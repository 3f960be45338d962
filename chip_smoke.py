"""Bring-up check: the search's device path on one TPU, end to end.

    python chip_smoke.py                # one chip: every phase below, in order
    python chip_smoke.py --four-chips   # a four-chip host: the sharded trainer

Phases, all in this one process (a chip belongs to one process at a time):

* device   — the first device must be a TPU; anything else is a failure.
* ask      — BO-GP (jax and pallas backends) and TPE (jax) against the numpy
             reference on one seeded mixed-space history (|H| = 2048, a pool
             of 4096): same top-1 candidate, scores within tolerance, no
             random fallback.
* walltime — a six-trial BO-GP investigation of the nano-100m ``train``
             walltime member at published widths (seq 1024, one chip),
             through ``Investigation.run``; then the ``flash`` and ``xla``
             kernels measured explicitly (bf16, batch 8), their forward
             logits compared, and the flash step's compiled text checked for
             the compiled Pallas kernel (``tpu_custom_call``).
* trainer  — ``repro.launch.train.main``: nano-100m, 4 steps, batch 8,
             seq 1024, bf16, 1x1 mesh; step-0 loss against ``model.loss`` in
             float32 at "highest" matmul precision on the same parameters
             and batch.
* server   — ``repro.launch.serve.main``: nano-100m, batch 4, prompt 128,
             8 generated tokens; every step's logits against a float32 full
             forward pass over prompt + generated tokens.

``--four-chips`` runs only the sharded trainer on a (data=2, model=2) mesh for
3 steps, compares its first step with the same step on a 1x1 mesh of device
0 (loss and gradient norm), and checks that a model-sharded weight has shards
on four distinct devices.

Each phase prints its result, its set-up time (the first call, which
compiles) and its step times.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed; any failure exits non-zero.  Compiled programs
go to the persistent compilation cache (``repro.launch.compile_cache``), so a
second run shows shorter set-up times.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "nano-100m"

# -- tolerances -------------------------------------------------------------
#: BO-GP EI surface, max |EI - EI_ref| / max EI_ref.  The device fits the GP
#: in float32 (at "highest" matmul precision) against a float64 reference.
#: With 1e-4 noise, the 2048-point Gram matrix has a condition number near
#: 1e7, so float32 arithmetic alone moves the surface by 7.0e-2 of its peak
#: with the jax backend on a CPU, and by 9.4e-2 on a TPU v5e; the tolerance
#: allows twice the CPU's figure.
EI_RTOL = 1.5e-1
#: TPE log-density ratio, absolute: float32 sums of at most 2048 Gaussian
#: kernels and the TPU's exp/log approximations, against float64 (3.0e-4
#: on a TPU v5e).
TPE_ATOL = 1e-3
#: flash vs xla forward logits at bf16, max |Δ| / max |logits|: two
#: attention orders of summation over bf16 activations, each rounding at
#: 2^-8 relative through 12 layers.
FLASH_RTOL = 5e-2
#: trainer step-0 loss (bf16 compute) vs float32 "highest" reference,
#: absolute, in nats: bf16 rounding of a mean over 8192 token losses.
LOSS_ATOL = 5e-2
#: decode logits (bf16 compute, cached keys/values) vs a float32 full
#: forward, max |Δ| / max |logits| over the compared steps.
DECODE_RTOL = 5e-2
#: sharded (2x2) vs 1x1 first step, relative: the same bf16 program
#: partitioned four ways sums its matmuls and reductions in another order.
SHARD_RTOL = 1e-2


class PhaseFailure(Exception):
    """A phase's output disagreed with its reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailure(what)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# -- ask ---------------------------------------------------------------------


def ask_space():
    """A mixed deployment space: two discrete, one categorical and one
    continuous dimension (the ask benchmark's shape)."""
    import numpy as np

    from repro.core import Dimension, ProbabilitySpace
    return ProbabilitySpace.make([
        Dimension.discrete("cpu", sorted({int(v) for v in
                                          np.linspace(1, 128, 40)})),
        Dimension.discrete("mem_gb", sorted({int(v) for v in
                                             np.linspace(1, 512, 40)})),
        Dimension.categorical("instance", [f"type-{i}" for i in range(12)]),
        Dimension.continuous("util_target", 0.1, 0.95),
    ])


def _adapter(space, history: int, seed: int):
    """A search adapter holding a seeded history of ``history`` trials with
    uniform random costs (a surface far from the model's smooth prior, so
    EI stays informative everywhere instead of vanishing)."""
    import numpy as np

    from repro.core import (ActionSpace, DiscoverySpace, FunctionExperiment,
                            SampleStore)
    from repro.core.optimizers.base import SearchAdapter, Trial
    rng = np.random.default_rng(seed)
    configs = [space.sample_configuration(rng) for _ in range(history)]
    y = rng.random(history)
    exp = FunctionExperiment(fn=lambda c: {"cost": 0.0}, properties=("cost",),
                             name="smoke-ask")
    ds = DiscoverySpace(space=space, actions=ActionSpace.make([exp]),
                        store=SampleStore(":memory:"))
    adapter = SearchAdapter(ds, "cost", "min")
    adapter.tell([Trial(c, float(v), "measured", i)
                  for i, (c, v) in enumerate(zip(configs, y))])
    return adapter


def phase_ask(history: int = 2048, pool: int = 4096, seed: int = 0) -> dict:
    import numpy as np

    from repro.core.optimizers import GPBayesOpt, TPE
    space = ask_space()
    adapter = _adapter(space, history, seed)
    candidates = GPBayesOpt._unseen_candidates(
        adapter, np.random.default_rng(seed + 1), pool)
    Xc = np.stack([space.encode(c) for c in candidates])
    X, y = GPBayesOpt._history_arrays(adapter)
    ok = [t for t in adapter.trials if t.value is not None]
    tpe_ref = TPE(seed=0, backend="numpy", max_candidates=pool)
    order = np.argsort([t.value for t in ok])
    n_good = max(1, int(np.ceil(tpe_ref.gamma * len(ok))))
    good = [ok[i].configuration for i in order[:n_good]]
    bad = [ok[i].configuration for i in order[n_good:]]

    def surface(opt):
        if isinstance(opt, TPE):
            return opt._score(space, good, bad, candidates)
        return opt._acquisition(X, y, Xc)

    results = {}
    for family, cls, backends in (("bo-gp", GPBayesOpt, ("jax", "pallas")),
                                  ("tpe", TPE, ("jax",))):
        ref_opt = cls(seed=0, backend="numpy", max_candidates=pool)
        ref = surface(ref_opt)
        ref_top = ref_opt.ask(adapter, np.random.default_rng(seed + 2))[0]
        for backend in backends:
            opt = cls(seed=0, backend=backend, max_candidates=pool)
            got, setup_s = _timed(lambda: surface(opt))
            _, step_s = _timed(lambda: surface(opt))
            top = opt.ask(adapter, np.random.default_rng(seed + 2))[0]
            label = f"{family}/{backend}"
            _check(top.score is not None,
                   f"{label}: ask fell back to a random proposal")
            _check(top.configuration == ref_top.configuration,
                   f"{label}: top-1 {top.configuration.as_dict()} != numpy "
                   f"{ref_top.configuration.as_dict()}")
            _check(bool(np.isfinite(got).all()),
                   f"{label}: non-finite scores")
            if family == "tpe":
                err, tol = float(np.abs(got - ref).max()), TPE_ATOL
                metric = "max|Δscore|"
            else:
                err = float(np.abs(got - ref).max() / np.abs(ref).max())
                tol, metric = EI_RTOL, "max|ΔEI|/max EI"
            _check(err <= tol, f"{label}: {metric} = {err:.3e} > {tol:g}")
            _check(int(np.argmax(got)) == int(np.argmax(ref)),
                   f"{label}: score argmax differs from numpy")
            results[label] = {"err": err, "tol": tol, "setup_s": setup_s,
                              "step_s": step_s}
            _say("ask", f"{label}: |H|={history} pool={len(candidates)} "
                        f"top-1 identical, no fallback, {metric} = {err:.3e} "
                        f"(tol {tol:g}); set-up {setup_s:.3f} s, "
                        f"repeat {step_s:.4f} s")
    return results


# -- walltime ----------------------------------------------------------------


def phase_walltime(seq_len: int = 1024, smoke: bool = False,
                   max_trials: int = 6, batch: int = 8) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.api import Investigation
    from repro.core.entities import Configuration
    from repro.workloads.llm import DeploymentSpaceFamily
    from repro.workloads.llm.connectors import LLMWalltimeConnector

    family = DeploymentSpaceFamily(ARCH, "train")
    spec = family.investigation_spec(seq_len=seq_len, devices=1,
                                     tier="walltime", smoke=smoke,
                                     optimizer="bo-gp", max_trials=max_trials)
    spec = dataclasses.replace(
        spec,
        optimizers=(dataclasses.replace(spec.optimizers[0], backend="jax"),),
        execution=dataclasses.replace(spec.execution, backend="serial"))
    result, run_s = _timed(lambda: Investigation(spec).run())
    summary = result.summary()
    trials = [t for _, t in result.events]
    _check(summary["failures"] == {} and len(trials) == max_trials
           and all(t.action == "measured" and t.value is not None
                   for t in trials),
           f"walltime: {len(trials)} trials, failures {summary['failures']}")
    for t in trials:
        c = t.configuration.as_dict()
        _say("walltime", f"trial {c['kernel']}/{c['precision']}/b{c['batch']}"
                         f"/{c['sharding']}: step_time_s = {t.value:.6f}")
    _say("walltime", f"investigation: {len(trials)} trials measured, 0 "
                     f"failed, best step_time_s = {summary['best']['value']:.6f}"
                     f" ({run_s:.1f} s incl. compiles)")

    # the kernel dimension, measured explicitly on the forward pass
    conn = LLMWalltimeConnector(ARCH, seq_len=seq_len, kind="prefill",
                                smoke=smoke)
    outs, steps = {}, {}
    for kernel in ("flash", "xla"):
        config = Configuration.make({"mesh": "1x1", "sharding": "replicate",
                                     "batch": batch, "kernel": kernel,
                                     "precision": "bf16"})
        dep, setup_s = _timed(lambda: conn.provision(config))
        props = conn.parse(conn.run(dep))
        step, params, b = dep.handle
        outs[kernel] = step(params, b)
        steps[kernel] = step, params, b
        _say("walltime", f"kernel={kernel} bf16 b{batch} s{seq_len}: "
                         f"set-up {setup_s:.2f} s, step_time_s = "
                         f"{props['step_time_s']:.6f}, tokens/s = "
                         f"{props['tokens_per_s']:.0f}")
    flash, xla = outs["flash"], outs["xla"]
    _check(bool(jnp.isfinite(flash).all()) and bool(jnp.isfinite(xla).all()),
           "walltime: non-finite forward logits")
    err = float(jnp.abs(flash - xla).max() / jnp.abs(xla).max())
    _check(err <= FLASH_RTOL,
           f"walltime: flash vs xla max|Δ|/max = {err:.3e} > {FLASH_RTOL:g}")
    step, params, b = steps["flash"]
    text = step.lower(params, b).compile().as_text()
    compiled_kernel = "tpu_custom_call" in text
    if jax.default_backend() == "tpu":
        _check(compiled_kernel, "walltime: flash step holds no compiled "
                                "Pallas kernel (tpu_custom_call)")
    _say("walltime", f"flash vs xla logits: max|Δ|/max = {err:.3e} "
                     f"(tol {FLASH_RTOL:g}); flash step tpu_custom_call "
                     f"present: {compiled_kernel}")
    return {"trials": len(trials), "flash_vs_xla": err,
            "tpu_custom_call": compiled_kernel}


# -- trainer -----------------------------------------------------------------


def _f32_model(cfg):
    import jax.numpy as jnp

    from repro.models.blocks import ModelOptions
    from repro.models.common import DTypePolicy
    from repro.models.model import LMModel
    return LMModel(cfg, ModelOptions(policy=DTypePolicy(
        param_dtype=jnp.float32, compute_dtype=jnp.float32)))


def phase_trainer(argv) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch import train

    out, run_s = _timed(lambda: train.main(argv))
    losses = out["losses"]
    _check(len(losses) > 0 and all(math.isfinite(v) for v in losses),
           f"trainer: losses {losses}")
    args = train.parse_args(argv)
    cfg = get_config(args.arch, smoke=args.smoke)
    model = _f32_model(cfg)
    params = train.initial_state(model, args)["params"]
    batch = train.data_pipeline(cfg, args).batch_at(0)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(model.loss)(params, batch)[0])
    err = abs(losses[0] - ref)
    _check(err <= LOSS_ATOL, f"trainer: step-0 loss {losses[0]:.6f} vs "
                             f"f32 reference {ref:.6f} (|Δ| {err:.3e})")
    times = out["step_times"]
    _say("trainer", f"{args.arch} b{args.batch} s{args.seq} "
                    f"{args.compute_dtype}: losses {losses}, step-0 loss vs "
                    f"f32 reference {ref:.6f}: |Δ| = {err:.3e} (tol "
                    f"{LOSS_ATOL:g}); step 0 (set-up) {times[0]:.2f} s, "
                    f"steps 1.. {[round(t, 4) for t in times[1:]]} s")
    return {"losses": losses, "loss_err": err, "step_times": times}


# -- server ------------------------------------------------------------------


def phase_server(argv) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve

    out, run_s = _timed(lambda: serve.main(argv))
    logits, prompts, tokens = out["logits"], out["prompts"], out["tokens"]
    arch = argv[argv.index("--arch") + 1]
    smoke = "--smoke" in argv
    cfg = get_config(arch, smoke=smoke)
    model = _f32_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    full = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(model.forward)(
            params, {"tokens": jnp.asarray(full)})[0])
    P = prompts.shape[1]
    ref = ref[:, P - 1:P - 1 + logits.shape[1]]
    _check(bool(np.isfinite(logits).all()), "server: non-finite logits")
    per_step = np.abs(logits - ref).max(axis=(0, 2)) / np.abs(ref).max()
    err = float(per_step.max())
    _check(err <= DECODE_RTOL, f"server: decode vs forward max|Δ|/max = "
                               f"{err:.3e} > {DECODE_RTOL:g}")
    _say("server", f"{arch} b{prompts.shape[0]} prompt {P} gen "
                   f"{logits.shape[1]}: per-step max|Δ|/max vs f32 forward "
                   f"{[float(f'{e:.3e}') for e in per_step]} (tol "
                   f"{DECODE_RTOL:g}); prefill incl. compile "
                   f"{out['prefill_ms']:.1f} ms, decode "
                   f"{out['tokens_per_s']:.1f} tok/s incl. its compile, "
                   f"phase {run_s:.1f} s")
    return {"decode_err": err}


# -- four chips --------------------------------------------------------------


def phase_four_chips(argv) -> dict:
    import jax

    from repro.launch import train

    sharded, run_s = _timed(lambda: train.main(argv + ["--model-axis", "2"]))
    single = train.main(argv + ["--stop-after", "1"],
                        devices=jax.devices()[:1])
    for key, label in (("losses", "loss"), ("grad_norms", "grad norm")):
        a, b = sharded[key][0], single[key][0]
        rel = abs(a - b) / abs(b)
        _check(rel <= SHARD_RTOL, f"four-chips: step-0 {label} 2x2 {a} vs "
                                  f"1x1 {b} (rel {rel:.3e})")
        _say("four-chips", f"step-0 {label}: 2x2 {a:.6f} vs 1x1 {b:.6f}, "
                           f"rel {rel:.3e} (tol {SHARD_RTOL:g})")
    leaves = jax.tree_util.tree_flatten_with_path(sharded["state"]["params"])[0]
    path, wq = next((p, x) for p, x in leaves
                    if getattr(p[-1], "key", None) == "wq")
    devices = {s.device for s in wq.addressable_shards}
    shard_shape = wq.addressable_shards[0].data.shape
    _check(len(devices) == 4 and shard_shape != wq.shape,
           f"four-chips: wq {wq.shape} shards {shard_shape} on "
           f"{len(devices)} devices")
    _say("four-chips", f"{jax.tree_util.keystr(path)} {wq.shape} "
                       f"{wq.sharding.spec}: shards {shard_shape} on "
                       f"{len(devices)} distinct devices; losses "
                       f"{sharded['losses']}; steps "
                       f"{[round(t, 4) for t in sharded['step_times']]} s")
    return {"losses": sharded["losses"]}


# -- driver ------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded trainer on a 2x2 mesh "
                         "(needs a four-chip host)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _say("device", f"FAIL: the first device is {dev.platform!r} "
                       f"({dev.device_kind}), not a TPU")
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        _say("device", f"FAIL: {len(devices)} device(s), need {want}")
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _say("device", f"{dev.platform} {dev.device_kind} x{len(devices)}; "
                   f"compile cache {cache} ({entries} entries)")

    train_argv = ["--arch", ARCH, "--batch", "8", "--seq", "1024",
                  "--compute-dtype", "bfloat16", "--log-every", "1"]
    if args.four_chips:
        phases = [("four-chips",
                   lambda: phase_four_chips(train_argv + ["--steps", "3"]))]
    else:
        phases = [
            ("ask", phase_ask),
            ("walltime", phase_walltime),
            ("trainer", lambda: phase_trainer(train_argv + ["--steps", "4"])),
            ("server", lambda: phase_server(
                ["--arch", ARCH, "--batch", "4", "--prompt-len", "128",
                 "--gen", "8"])),
        ]
    failed = []
    for name, run in phases:
        try:
            _, took = _timed(run)
            _say(name, f"PASS ({took:.1f} s)")
        except Exception as err:  # report every phase, then fail
            failed.append(name)
            _say(name, f"FAIL: {type(err).__name__}: {err}")
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _say("device", f"compile cache now holds {entries} entries")
    if failed:
        _say("device", f"failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
