# --- multi-pod dry-run driver -------------------------------------------------
# Lowers + compiles every (architecture × input shape) cell for the production
# mesh (16×16 single pod; 2×16×16 multi-pod), prints memory_analysis() and
# cost_analysis(), and derives the three roofline terms per cell.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch xlstm-125m --mesh single
#
# Run as a script it asks XLA for 512 placeholder host devices, before any
# backend is touched (jax locks the device count on first init).  A process
# that only imports this module keeps its own devices.

import argparse
import os
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCHITECTURES, SHAPES, ShapeSpec,
                           cell_applicability, get_config)
from repro.distributed.sharding import (DeploymentConfig, batch_specs,
                                        default_deployment)
from repro.launch.mesh import make_production_mesh
from repro.models.model import LMModel
from repro.roofline.analysis import analyze_compiled
from repro.roofline.hw import HW_V5E
from repro.serving.serve_step import make_decode_step, make_prefill_step
from repro.training.train_step import init_train_state, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def batch_structs(cfg, shape, kind: str):
    B, S = shape.global_batch, shape.seq_len
    if kind == "decode":
        S_in = 1
    else:
        S_in = S
    out = {}
    if cfg.uses_tokens:
        out["tokens"] = jax.ShapeDtypeStruct((B, S_in), jnp.int32)
    else:
        out["embeds"] = jax.ShapeDtypeStruct((B, S_in, cfg.frontend_dim),
                                             jnp.bfloat16)
    if kind == "train":
        out["labels"] = jax.ShapeDtypeStruct((B, S_in), jnp.int32)
    return out


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N·D for inference (N = active
    params excluding the token-embedding table, D = tokens processed)."""
    n = cfg.active_param_count()
    if cfg.uses_tokens:
        n -= cfg.vocab_size * cfg.d_model
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one new token per sequence
    return 2.0 * n * tokens


def lower_cell(arch: str, shape_name, mesh, deployment=None):
    """Build and lower the step function for one cell.  Returns (lowered,
    meta) — compile separately so callers can time the phases.

    ``shape_name`` is a key of :data:`~repro.configs.SHAPES` or a
    :class:`~repro.configs.ShapeSpec` directly (the LLM deployment-space
    family lowers off-matrix sequence lengths via
    :func:`~repro.configs.custom_shape`)."""
    cfg = get_config(arch)
    shape = shape_name if isinstance(shape_name, ShapeSpec) \
        else SHAPES[shape_name]
    if deployment is None:
        deployment = default_deployment(cfg, mesh, shape_kind=shape.kind,
                                        global_batch=shape.global_batch,
                                        seq_len=shape.seq_len)
    model = LMModel(cfg, deployment.model_options())
    kind = shape.kind

    if kind == "train":
        step, sspecs, bspecs = make_train_step(model, deployment, mesh)
        state_struct = jax.eval_shape(
            lambda k: init_train_state(model, k), jax.random.PRNGKey(0))
        lowered = step.lower(state_struct, batch_structs(cfg, shape, kind))
    elif kind == "prefill":
        fn, _, _ = make_prefill_step(model, deployment, mesh,
                                     capacity=shape.seq_len)
        params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        lowered = fn.lower(params_struct, batch_structs(cfg, shape, kind))
    elif kind == "decode":
        fn, _, _ = make_decode_step(model, deployment, mesh)
        params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache_struct = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        index = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = fn.lower(params_struct, batch_structs(cfg, shape, kind),
                           cache_struct, index)
    else:
        raise ValueError(kind)
    return lowered, {"cfg": cfg, "shape": shape, "deployment": deployment}


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool = False,
                deployment: Optional[DeploymentConfig] = None,
                mesh=None, verbose: bool = True, save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(str(d) for d in mesh.devices.shape)
    ok, reason = cell_applicability(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_desc}
    if not ok:
        result.update(status=f"skip({reason})")
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {mesh_desc}: SKIP — {reason}")
        return result

    t0 = time.time()
    with mesh:
        lowered, meta = lower_cell(arch, shape_name, mesh, deployment)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem_repr = None
        try:
            mem_repr = str(compiled.memory_analysis())
        except Exception as e:  # pragma: no cover
            mem_repr = f"<memory_analysis unavailable: {e}>"
        chips = mesh.devices.size
        mesh_groups = dict(zip(mesh.axis_names, mesh.devices.shape))
        report = analyze_compiled(
            compiled, arch, shape_name, mesh_desc, chips, mesh_groups,
            model_flops=model_flops_for(cfg, shape))

    result.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory_analysis=mem_repr,
        roofline=report.summary(),
        hlo_flops_per_device=report.hlo_flops,
        hlo_bytes_per_device=report.hlo_bytes,
        collective_bytes=report.collective,
        collective_counts=report.collective_counts,
        model_flops=report.model_flops,
        deployment=_deployment_json(meta["deployment"]),
    )
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_desc}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"         memory_analysis: {mem_repr}")
        print(f"         cost_analysis: flops/dev={report.hlo_flops:.3e} "
              f"bytes/dev={report.hlo_bytes:.3e}")
        print(f"         roofline: {report.summary()}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        path = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_desc}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def _deployment_json(dep: DeploymentConfig) -> dict:
    d = dict(dep.__dict__)
    d["rules"] = dict(dep.rules)
    return d


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    archs = sorted(ARCHITECTURES) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    failures = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        for arch in archs:
            for shape_name in shapes:
                try:
                    results.append(dryrun_cell(arch, shape_name, mesh=mesh,
                                               multi_pod=multi_pod))
                except Exception as e:
                    failures += 1
                    print(f"[dryrun] {arch} × {shape_name} "
                          f"(multi_pod={multi_pod}): FAILED — {e}")
                    traceback.print_exc()
                    if args.fail_fast:
                        raise
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_skip = sum(1 for r in results if str(r.get("status", "")).startswith("skip"))
    print(f"\n[dryrun] done: {n_ok} ok, {n_skip} skipped, {failures} failed")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
