"""Tiered measurement connectors for the LLM deployment-space family.

Both tiers are phased through the actuation lifecycle
(:mod:`repro.core.connector`) and observe the same headline metrics
(``step_time_s``, ``tokens_per_s``) so their values live on one scale and a
space measured at the fast tier can seed §IV transfer into a slow-tier
sibling:

* :class:`LLMDryrunConnector` — the fast tier: scores a configuration with
  the analytic roofline cost model
  (:func:`~repro.roofline.estimate.estimate_deployment` — the closed-form
  counterpart of :class:`~repro.tuning.experiments.DryrunRooflineConnector`'s
  compiled-HLO path, same :class:`~repro.roofline.hw.HWSpec` constants, same
  max-of-terms step time).  Thousands of points per second, so a whole
  family member is measurable exhaustively.  A configuration whose HBM
  residency exceeds the chip is the paper's "non-deployable point":
  terminal :class:`~repro.core.actions.MeasurementError` at parse.
* :class:`LLMWalltimeConnector` — the slow tier: provisions the real model
  (smoke-scaled config) with the configuration's kernel variant and compute
  dtype, compiles the jitted train/serve step, and times it on the local
  devices.  A configuration whose mesh split wants more chips than the host
  has — or that runs out of device memory at compile or run time — is
  non-deployable here even when the cost model likes it, which is exactly
  the disagreement tiering exists to surface.  Any other failure is a fault
  of the program or the device, not a property of the configuration: it
  propagates and stops the investigation rather than posing as a result.

Identity: the per-member knobs (arch, kind, seq_len, devices, hw) live in
the connector *parameterization*, not in Ω — so two family members with
identical dimensions but different sequence lengths are distinct Discovery
Spaces in the catalog (the paper's FT-TRANS pattern), while the per-point
knobs (mesh split, sharding, batch, kernel, precision) are the dimensions
the search walks.  All phase timing runs on the injectable clock.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence, Union

from ...core.actions import MeasurementError
from ...core.clock import SYSTEM_CLOCK, Clock
from ...core.connector import Deployment, ExperimentConnector
from ...core.entities import Configuration
from ...launch.mesh import parse_mesh_split
from ...roofline.hw import HWSpec, HW_V4_LIKE, HW_V5E

__all__ = ["LLMDryrunConnector", "LLMWalltimeConnector", "resolve_hw",
           "KERNEL_IMPLS"]

_HW_BY_NAME = {hw.name: hw for hw in (HW_V5E, HW_V4_LIKE)}

#: kernel dimension value → repo attention implementation
KERNEL_IMPLS = {"ref": "ref", "xla": "xla", "flash": "pallas"}


def resolve_hw(hw: Union[str, HWSpec]) -> HWSpec:
    """Accept an :class:`HWSpec` or its JSON-friendly name."""
    if isinstance(hw, HWSpec):
        return hw
    if hw not in _HW_BY_NAME:
        raise ValueError(f"unknown hardware {hw!r} "
                         f"(known: {sorted(_HW_BY_NAME)})")
    return _HW_BY_NAME[hw]


def _out_of_memory(err: Exception) -> bool:
    """True for the device running out of memory — XLA's RESOURCE_EXHAUSTED
    status, raised at compile time (the program does not fit) or at run
    time (an allocation failed)."""
    return "RESOURCE_EXHAUSTED" in str(err)


def _decode(configuration: Configuration, devices: int) -> dict:
    """Validate and unpack a family configuration.  A mesh split that does
    not multiply out to the member's topology is the configuration's fault:
    terminal, never retried."""
    d = configuration.as_dict()
    data, model = parse_mesh_split(str(d["mesh"]))
    if data * model != devices:
        raise MeasurementError(
            f"non-deployable: mesh {d['mesh']} needs {data * model} chips "
            f"on a {devices}-chip topology")
    return {"data": data, "model": model,
            "sharding": str(d["sharding"]), "batch": int(d["batch"]),
            "kernel": str(d["kernel"]), "precision": str(d["precision"])}


class LLMDryrunConnector(ExperimentConnector):
    """Fast-tier analytic roofline scoring (see module docstring)."""

    name = "llm-dryrun"
    version = "1"

    def __init__(self, arch: str, seq_len: int, devices: int,
                 kind: str = "train", hw: Union[str, HWSpec] = HW_V5E,
                 hbm_fraction: float = 1.0, clock: Clock = SYSTEM_CLOCK):
        self.arch = arch
        self.seq_len = int(seq_len)
        self.devices = int(devices)
        self.kind = kind
        self.hw = resolve_hw(hw)
        self.hbm_fraction = float(hbm_fraction)
        self.clock = clock

    @property
    def parameterization(self) -> Mapping[str, Any]:
        return {"arch": self.arch, "kind": self.kind, "seq": self.seq_len,
                "devices": self.devices, "hw": self.hw.name}

    @property
    def observed_properties(self) -> Sequence[str]:
        return ("step_time_s", "compute_s", "memory_s", "collective_s",
                "bytes_per_device", "hbm_resident_bytes", "tokens_per_s",
                "cost_per_1m_tokens")

    def provision(self, configuration: Configuration) -> Deployment:
        from ...configs import get_config  # deferred: pulls the model zoo
        decoded = _decode(configuration, self.devices)
        return Deployment(
            ident=f"llm-dryrun-{configuration.digest[:12]}",
            configuration=configuration, created_at=self.clock.time(),
            handle=(get_config(self.arch), decoded))

    def run(self, deployment: Deployment) -> Any:
        from ...roofline.estimate import estimate_deployment
        cfg, decoded = deployment.handle
        return estimate_deployment(
            cfg, seq_len=self.seq_len, batch_per_replica=decoded["batch"],
            data=decoded["data"], model=decoded["model"], kind=self.kind,
            sharding=decoded["sharding"], kernel=decoded["kernel"],
            precision=decoded["precision"], hw=self.hw)

    def parse(self, raw: Any) -> Mapping[str, float]:
        if not raw.fits_hbm(self.hbm_fraction):
            raise MeasurementError(
                f"over HBM: {raw.hbm_resident_bytes / 1e9:.1f} GB resident "
                f"> {self.hw.hbm_bytes * self.hbm_fraction / 1e9:.1f} GB")
        return raw.properties()


class LLMWalltimeConnector(ExperimentConnector):
    """Slow-tier timed microbench of the real model (see module docstring).

    ``devices`` defaults to 1 — the honest local topology; larger splits in
    Ω fail provisioning as non-deployable on this host.  ``smoke`` (default)
    uses the architecture's reduced config so the compile fits CI budgets.
    """

    name = "llm-walltime"
    version = "1"
    needs_device = True

    def __init__(self, arch: str, seq_len: int, devices: int = 1,
                 kind: str = "train", repeats: int = 3, smoke: bool = True,
                 clock: Clock = SYSTEM_CLOCK):
        self.arch = arch
        self.seq_len = int(seq_len)
        self.devices = int(devices)
        self.kind = kind
        self.repeats = int(repeats)
        self.smoke = bool(smoke)
        self.clock = clock

    @property
    def parameterization(self) -> Mapping[str, Any]:
        return {"arch": self.arch, "kind": self.kind, "seq": self.seq_len,
                "devices": self.devices, "repeats": self.repeats,
                "smoke": self.smoke}

    @property
    def observed_properties(self) -> Sequence[str]:
        return ("step_time_s", "tokens_per_s")

    def provision(self, configuration: Configuration) -> Deployment:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ...configs import get_config
        from ...models.attention import AttnOptions
        from ...models.blocks import ModelOptions
        from ...models.common import DTypePolicy
        from ...models.model import LMModel
        from ...roofline.estimate import PRECISION_BYTES

        decoded = _decode(configuration, self.devices)
        if self.devices > len(jax.devices()):
            raise MeasurementError(
                f"non-deployable: topology wants {self.devices} chips, "
                f"host has {len(jax.devices())}")
        if decoded["kernel"] not in KERNEL_IMPLS:
            raise MeasurementError(
                f"non-deployable: unknown kernel {decoded['kernel']!r}")
        cfg = get_config(self.arch, smoke=self.smoke)
        compute = (jnp.bfloat16 if decoded["precision"] == "bf16"
                   else jnp.float32)
        assert decoded["precision"] in PRECISION_BYTES
        chunk = max(16, min(self.seq_len, 128))
        model = LMModel(cfg, ModelOptions(
            attn=AttnOptions(impl=KERNEL_IMPLS[decoded["kernel"]],
                             q_chunk=chunk, kv_chunk=chunk),
            policy=DTypePolicy(param_dtype=jnp.float32,
                               compute_dtype=compute)))
        batch, seq = decoded["batch"], self.seq_len
        rng = np.random.default_rng(0)
        b = {}
        if cfg.uses_tokens:
            b["tokens"] = rng.integers(0, cfg.vocab_size, (batch, seq))
        else:
            b["embeds"] = rng.normal(
                size=(batch, seq, cfg.frontend_dim)).astype("float32")
        if self.kind == "train":
            b["labels"] = rng.integers(0, cfg.vocab_size, (batch, seq))
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params = model.init(jax.random.PRNGKey(0))

        if self.kind == "train":
            @jax.jit
            def step(params, batch):
                loss, _ = model.loss(params, batch)
                return loss
        else:
            # prefill/decode microbench: the forward pass over seq_len (the
            # decode-shaped single-token step needs a served cache; the
            # prefill-shaped forward is the slow-tier proxy for both)
            @jax.jit
            def step(params, batch):
                out = model.forward(params, batch)
                return out[0] if isinstance(out, tuple) else out

        try:
            jax.block_until_ready(step(params, b))  # compile
        except Exception as e:
            if not _out_of_memory(e):
                raise
            raise MeasurementError(f"non-deployable: {type(e).__name__}: {e}")
        return Deployment(
            ident=f"llm-walltime-{configuration.digest[:12]}",
            configuration=configuration, created_at=self.clock.time(),
            handle=(step, params, b), meta={"batch": batch, "seq": seq})

    def run(self, deployment: Deployment) -> Any:
        import jax
        step, params, b = deployment.handle
        try:
            times = []
            for _ in range(self.repeats):
                t0 = self.clock.monotonic()
                jax.block_until_ready(step(params, b))
                times.append(self.clock.monotonic() - t0)
        except Exception as e:
            if not _out_of_memory(e):
                raise
            raise MeasurementError(f"non-deployable: {e}")
        return min(times), deployment.meta

    def parse(self, raw: Any) -> Mapping[str, float]:
        best, meta = raw
        # a virtual clock can legitimately observe zero elapsed time
        best = max(best, 1e-9)
        return {"step_time_s": best,
                "tokens_per_s": meta["batch"] * meta["seq"] / best}
