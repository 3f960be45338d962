"""End-to-end training launcher: data → sharded train step → checkpoints,
with restart-after-failure and elastic re-meshing.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \\
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Run as a script, it keeps compiled steps in the persistent compilation
cache (:mod:`repro.launch.compile_cache`).

The launcher is deliberately structured the way a 1000-node job would be:
  1. build/restore: if the checkpoint dir has a latest step, resume from it
     (restart-after-failure path — also the entry point after an elastic
     re-mesh, since checkpoints are mesh-independent);
  2. deterministic data cursor = global step (stream is seekable, so resume
     needs no data-state persistence);
  3. checkpoint every N steps (async), retain K.
"""

import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed.sharding import default_deployment, named_sharding_tree
from repro.checkpoint.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def build(args, devices=None):
    """Mesh, model and jitted step.  ``devices`` (default: all) are the
    devices the ``data × model`` mesh spans."""
    devices = list(jax.devices() if devices is None else devices)
    model_axis = args.model_axis if args.model_axis else 1
    data_axis = len(devices) // model_axis
    mesh = make_mesh((data_axis, model_axis), ("data", "model"), devices)
    cfg = get_config(args.arch, smoke=args.smoke)
    deployment = default_deployment(cfg, mesh, shape_kind="train",
                                    global_batch=args.batch, seq_len=args.seq)
    deployment = replace(deployment, microbatches=args.microbatches,
                         compute_dtype=args.compute_dtype)
    model = LMModel(cfg, deployment.model_options())
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                          total_steps=args.steps)
    step_fn, state_specs, bspecs = make_train_step(model, deployment, mesh,
                                                   opt_cfg)
    return mesh, cfg, model, deployment, step_fn, state_specs, bspecs


def initial_state(model, args) -> dict:
    """The fresh (un-restored) training state: a function of the run's
    arguments only, so a caller can rebuild the step-0 parameters."""
    return init_train_state(model, jax.random.PRNGKey(args.steps))


def data_pipeline(cfg, args) -> TokenPipeline:
    """The run's seekable token stream; ``batch_at(step)`` is its batch."""
    return TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq,
                                    global_batch=args.batch, seed=13))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--stop-after", type=int, default=0,
                    help="simulate failure: exit after N steps")
    return ap.parse_args(argv)


def main(argv=None, devices=None) -> dict:
    """Train; returns the run's summary, its per-step losses, gradient norms
    and wall times (step 0's includes compiling), and the final (sharded)
    state."""
    args = parse_args(argv)
    mesh, cfg, model, deployment, step_fn, state_specs, bspecs = build(
        args, devices)
    with mesh:
        mgr = None
        start_step = 0
        state = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=3,
                                    save_every=args.ckpt_every)
            latest = mgr.latest_step()
            if latest is not None:
                template = jax.eval_shape(
                    lambda k: init_train_state(model, k), jax.random.PRNGKey(0))
                shardings = named_sharding_tree(state_specs, mesh)
                state, manifest = mgr.restore_latest(template, shardings)
                start_step = int(manifest["step"])
                print(f"[train] restored checkpoint at step {start_step}")
        if state is None:
            state = jax.device_put(initial_state(model, args),
                                   named_sharding_tree(state_specs, mesh))

        data = data_pipeline(cfg, args)
        data.start(cursor=start_step)

        losses, grad_norms, step_times = [], [], []

        def summary() -> dict:
            return {"first_loss": losses[0] if losses else None,
                    "last_loss": losses[-1] if losses else None,
                    "steps_run": len(losses), "resumed_from": start_step,
                    "losses": losses, "grad_norms": grad_norms,
                    "step_times": step_times, "state": state}

        t0 = time.time()
        for step in range(start_step, args.steps):
            cursor, batch = next(data)
            assert cursor == step, f"data cursor {cursor} != step {step}"
            t_step = time.perf_counter()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            step_times.append(time.perf_counter() - t_step)
            losses.append(loss)
            grad_norms.append(float(metrics["grad_norm"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {grad_norms[-1]:.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)")
            if mgr is not None and mgr.should_save(step + 1):
                mgr.save(step + 1, state, {"loss": loss})
            if args.stop_after and (step + 1 - start_step) >= args.stop_after:
                # simulated hard failure: NO final checkpoint — restart must
                # recover from the last periodic one.  The failure loses
                # future work, not durability: an in-flight async save of an
                # *earlier* step still lands (atomic tmp+rename), so drain it
                # before "crashing" — otherwise resume races the save thread.
                if mgr is not None:
                    mgr.wait()
                print(f"[train] simulated failure after {args.stop_after} steps")
                data.stop()
                return summary()
        data.stop()
        # `losses` is empty when resuming a run that already completed
        # (start_step == steps): nothing ran, nothing new to checkpoint.
        if mgr is not None and losses:
            mgr.save(start_step + len(losses), state, {"loss": losses[-1]},
                     async_=False)
            mgr.wait()
    return summary()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = main()
    del out["state"]
    print(f"[train] done: {out}")
