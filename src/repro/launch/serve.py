"""Serving launcher: prefill + batched decode over the sharded serving path.

The production serving loop:
  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m --smoke \\
      --batch 4 --prompt-len 32 --gen 16

Run as a script, it keeps compiled steps in the persistent compilation
cache (:mod:`repro.launch.compile_cache`).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import default_deployment
from repro.launch.mesh import make_mesh
from repro.models.model import LMModel
from repro.serving.serve_step import make_decode_step, make_prefill_step


def main(argv=None) -> dict:
    """Prefill the prompts, then decode greedily.  Returns timings, the
    prompts, the generated tokens (B, gen) and the logits behind each of
    them (B, gen, V): prefill's last position, then one per decode step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args(argv)

    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: nothing to decode")
    capacity = args.prompt_len + args.gen
    deployment = default_deployment(cfg, mesh, shape_kind="decode",
                                    global_batch=args.batch)
    model = LMModel(cfg, deployment.model_options())

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        prefill, _, _ = make_prefill_step(model, deployment, mesh, capacity)
        decode, _, _ = make_decode_step(model, deployment, mesh)

        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        batch = {"tokens": jnp.asarray(prompts)} if cfg.uses_tokens else \
            {"embeds": jnp.asarray(rng.normal(
                size=(args.batch, args.prompt_len, cfg.frontend_dim)),
                jnp.float32)}

        t0 = time.time()
        logits, caches = prefill(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t_prefill = time.time() - t0

        generated = [np.asarray(tok)]
        step_logits = [logits]
        t0 = time.time()
        for i in range(args.gen - 1):
            step_batch = {"tokens": tok[:, None]} if cfg.uses_tokens else \
                {"embeds": jnp.zeros((args.batch, 1, cfg.frontend_dim),
                                     jnp.float32)}
            logits, caches = decode(params, step_batch, caches,
                                    args.prompt_len + i)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            generated.append(np.asarray(tok))
            step_logits.append(logits)
        t_decode = time.time() - t0

    out = np.stack(generated, axis=1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] {cfg.name}: prefill {args.prompt_len} tok × {args.batch} "
          f"seqs in {t_prefill * 1e3:.0f} ms; decoded {args.gen - 1} steps at "
          f"{tps:.1f} tok/s")
    print(f"[serve] sample continuation (seq 0): {out[0][:12].tolist()}")
    return {"prefill_ms": t_prefill * 1e3, "tokens_per_s": tps,
            "prompts": prompts, "tokens": out,
            "logits": np.stack([np.asarray(x, np.float32) for x in step_logits],
                               axis=1)}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
