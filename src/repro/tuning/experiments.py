"""Action-space experiments over deployment configurations.

Both experiments are phased through the actuation lifecycle
(:mod:`repro.core.connector`): *provision* is the deployment step (building
the model and compiling the jitted step on the production mesh), *run* is
the measurement proper (roofline analysis of the compiled artifact / the
timed step), *parse* shapes the properties, *teardown* is free (compiled
artifacts are process-local and garbage-collected).  The public classes are
compatibility shims — :class:`~repro.core.connector.LifecycleExperiment`
subclasses with the historical constructor signatures and identities — so
stored provenance reconciles and optimizer trajectories stay draw-for-draw
with the monolithic originals.

* :class:`DryrunRooflineExperiment` — provision = ``jit(step).lower()
  .compile()`` on the production mesh; run = trip-corrected roofline terms
  from the compiled artifact (the honest measurement available on this
  CPU-only container; identical interface to a wall-clock experiment on real
  TPUs).  Non-compiling or over-HBM configurations raise
  :class:`MeasurementError` — the paper's "non-deployable points".
* :class:`WalltimeExperiment` — real wall-clock timing of a reduced-config
  step on the local device (used by the optimizer benchmarks so that the
  paper-validation spaces contain genuinely *measured* data).

Both are hermetic: identity = (name, version, parameterization) where the
parameterization pins (arch, shape, mesh, hw) — so samples reconcile across
processes through the common context, and a different mesh or hardware is a
*different* Discovery Space (which is exactly what RSSC then bridges).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

from ..core.actions import MeasurementError
from ..core.clock import SYSTEM_CLOCK, Clock
from ..core.connector import (Deployment, ExperimentConnector,
                              LifecycleExperiment, PricingModel, RetryPolicy)
from ..core.entities import Configuration
from ..roofline.hw import HWSpec, HW_V5E

__all__ = ["DryrunRooflineExperiment", "WalltimeExperiment",
           "DryrunRooflineConnector", "WalltimeConnector"]


class DryrunRooflineConnector(ExperimentConnector):
    """Phased dry-run roofline measurement (see module docstring)."""

    name = "dryrun-roofline"
    version = "1"

    def __init__(self, arch: str, shape_name: str, mesh, hw: HWSpec = HW_V5E,
                 hbm_limit: Optional[float] = None,
                 clock: Clock = SYSTEM_CLOCK):
        self.arch = arch
        self.shape_name = shape_name
        self.mesh = mesh
        self.hw = hw
        self.hbm_limit = hbm_limit
        # every phase timestamp/duration this connector records goes through
        # the injectable clock, so virtual-clock specs and trace replays of
        # tuning experiments are deterministic (a FakeClock legitimately
        # reports zero compile time)
        self.clock = clock

    @property
    def parameterization(self) -> Mapping[str, Any]:
        return {"arch": self.arch, "shape": self.shape_name,
                "mesh": "x".join(map(str, self.mesh.devices.shape)),
                "hw": self.hw.name}

    @property
    def observed_properties(self) -> Sequence[str]:
        return ("compute_s", "memory_s", "collective_s", "step_time_s",
                "roofline_fraction", "hlo_flops", "bytes_per_device",
                "compile_s")

    def provision(self, configuration: Configuration) -> Deployment:
        """Deploy: translate the configuration and compile on the mesh.  A
        non-compiling configuration is the configuration's fault, not the
        infrastructure's — terminal :class:`MeasurementError`, no retry."""
        # imports deferred: this experiment requires the dry-run device env
        from ..configs import SHAPES, get_config
        from ..launch.dryrun import lower_cell
        from .deployment import deployment_from_configuration

        cfg = get_config(self.arch)
        shape = SHAPES[self.shape_name]
        dep = deployment_from_configuration(
            configuration, cfg, self.mesh, shape_kind=shape.kind,
            global_batch=shape.global_batch, seq_len=shape.seq_len)
        created_at = self.clock.time()
        t0 = self.clock.monotonic()
        try:
            with self.mesh:
                lowered, _ = lower_cell(self.arch, self.shape_name, self.mesh,
                                        dep)
                compiled = lowered.compile()
        except Exception as e:
            raise MeasurementError(f"non-deployable: {type(e).__name__}: {e}")
        compile_s = self.clock.monotonic() - t0
        return Deployment(
            ident=f"dryrun-{configuration.digest[:12]}",
            configuration=configuration, created_at=created_at,
            handle=compiled, meta={"compile_s": compile_s, "cfg": cfg,
                                   "shape": shape})

    def run(self, deployment: Deployment) -> Any:
        from ..launch.dryrun import model_flops_for
        from ..roofline.analysis import analyze_compiled

        cfg = deployment.meta["cfg"]
        shape = deployment.meta["shape"]
        chips = self.mesh.devices.size
        groups = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        report = analyze_compiled(
            deployment.handle, self.arch, self.shape_name,
            "x".join(map(str, self.mesh.devices.shape)), chips, groups,
            model_flops=model_flops_for(cfg, shape), hw=self.hw)
        return report, deployment.meta["compile_s"]

    def parse(self, raw: Any) -> Mapping[str, float]:
        report, compile_s = raw
        if (self.hbm_limit is not None and report.bytes_per_device is not None
                and report.bytes_per_device > self.hbm_limit):
            raise MeasurementError(
                f"over HBM: {report.bytes_per_device / 1e9:.1f} GB "
                f"> {self.hbm_limit / 1e9:.1f} GB")
        return DryrunRooflineExperiment._report_properties(report, compile_s)


class DryrunRooflineExperiment(LifecycleExperiment):
    """Compatibility shim: :class:`DryrunRooflineConnector` behind the
    historical constructor/identity (provenance reconciles; see module
    docstring).  ``retry``/``pricing``/``clock`` are new, optional, and —
    when left at their defaults — change nothing observable."""

    def __init__(self, arch: str, shape_name: str, mesh, hw: HWSpec = HW_V5E,
                 hbm_limit: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None,
                 pricing: Optional[PricingModel] = None,
                 clock: Clock = SYSTEM_CLOCK):
        super().__init__(
            DryrunRooflineConnector(arch, shape_name, mesh, hw=hw,
                                    hbm_limit=hbm_limit, clock=clock),
            retry=retry, pricing=pricing, clock=clock)

    @staticmethod
    def _report_properties(report, compile_s: float) -> Mapping[str, float]:
        out = {
            "compute_s": report.compute_s,
            "memory_s": report.memory_s,
            "collective_s": report.collective_s,
            "step_time_s": report.step_time_s,
            "roofline_fraction": report.roofline_fraction,
            "hlo_flops": report.hlo_flops,
            "compile_s": compile_s,
        }
        # A report without a byte count must OMIT bytes_per_device, never
        # record 0.0: a zero sentinel silently satisfies any memory SLA
        # (`bytes_per_device <= limit`), while constraint evaluation treats
        # a missing property as infeasible.  (NaN is no alternative —
        # sqlite3 binds float('nan') as NULL, corrupting the read path.)
        if report.bytes_per_device is not None:
            out["bytes_per_device"] = float(report.bytes_per_device)
        return out


class WalltimeConnector(ExperimentConnector):
    """Phased wall-clock step timing (see module docstring): provision
    builds + compiles the jitted step, run times it."""

    name = "walltime"
    version = "1"
    needs_device = True

    def __init__(self, arch: str, repeats: int = 3, compute_dtype="float32",
                 arch_scale: float = 1.0, clock: Clock = SYSTEM_CLOCK):
        self.arch = arch
        self.repeats = repeats
        self.compute_dtype = compute_dtype
        self.arch_scale = arch_scale
        # injectable timing source (see DryrunRooflineConnector.__init__)
        self.clock = clock

    @property
    def parameterization(self) -> Mapping[str, Any]:
        return {"arch": self.arch, "repeats": self.repeats,
                "scale": self.arch_scale, "dtype": str(self.compute_dtype)}

    @property
    def observed_properties(self) -> Sequence[str]:
        return ("step_ms", "tokens_per_s")

    def provision(self, configuration: Configuration) -> Deployment:
        import jax
        import numpy as np

        from ..configs import get_config
        from ..models.attention import AttnOptions
        from ..models.blocks import ModelOptions
        from ..models.model import LMModel

        d = configuration.as_dict()
        batch = int(d.get("batch", 2))
        seq = int(d.get("seq", 64))
        q_chunk = int(d.get("attn_q_chunk", 64))
        remat = str(d.get("remat", "none"))
        cfg = get_config(self.arch, smoke=True)
        model = LMModel(cfg, ModelOptions(
            attn=AttnOptions(impl="xla", q_chunk=q_chunk, kv_chunk=q_chunk),
            remat=remat))
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        b = {"labels": rng.integers(0, cfg.vocab_size, (batch, seq))}
        if cfg.uses_tokens:
            b["tokens"] = rng.integers(0, cfg.vocab_size, (batch, seq))
        else:
            b["embeds"] = rng.normal(size=(batch, seq, cfg.frontend_dim)) \
                .astype("float32")
        b = {k: jax.numpy.asarray(v) for k, v in b.items()}

        @jax.jit
        def step(params, batch):
            loss, m = model.loss(params, batch)
            return loss

        try:
            step(params, b).block_until_ready()  # compile
        except Exception as e:
            raise MeasurementError(f"non-deployable: {e}")
        return Deployment(
            ident=f"walltime-{configuration.digest[:12]}",
            configuration=configuration, created_at=self.clock.time(),
            handle=(step, params, b),
            meta={"batch": batch, "seq": seq})

    def run(self, deployment: Deployment) -> Any:
        step, params, b = deployment.handle
        try:
            times = []
            for _ in range(self.repeats):
                t0 = self.clock.monotonic()
                step(params, b).block_until_ready()
                times.append(self.clock.monotonic() - t0)
        except Exception as e:
            raise MeasurementError(f"non-deployable: {e}")
        return min(times), deployment.meta

    def parse(self, raw: Any) -> Mapping[str, float]:
        best, meta = raw
        # a virtual clock can legitimately observe zero elapsed time
        best = max(best, 1e-9)
        return {"step_ms": best * 1e3,
                "tokens_per_s": meta["batch"] * meta["seq"] / best}


class WalltimeExperiment(LifecycleExperiment):
    """Compatibility shim: :class:`WalltimeConnector` behind the historical
    constructor/identity."""

    def __init__(self, arch: str, repeats: int = 3, compute_dtype="float32",
                 arch_scale: float = 1.0,
                 retry: Optional[RetryPolicy] = None,
                 pricing: Optional[PricingModel] = None,
                 clock: Clock = SYSTEM_CLOCK):
        super().__init__(
            WalltimeConnector(arch, repeats=repeats,
                              compute_dtype=compute_dtype,
                              arch_scale=arch_scale, clock=clock),
            retry=retry, pricing=pricing, clock=clock)
