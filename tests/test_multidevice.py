"""Multi-device integration tests (subprocess with 8 placeholder devices):
pipeline parallelism, compressed cross-pod gradient sync, elastic-mesh
checkpoint restore.  Each runs in its own process because jax device count
locks at first init.

On hosts where the forced-host-platform flag cannot provide the devices
(e.g. a GPU/TPU backend pinned by env), the tests SKIP rather than fail —
probed once per session below."""

import functools
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUIRED_DEVICES = 8


def _env(devices: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


@functools.lru_cache(maxsize=None)
def _forced_device_count(devices: int = REQUIRED_DEVICES) -> int:
    """How many devices a fresh subprocess actually gets under the flag.

    Cached, and only probed from inside a test body (not at collection) so
    deselected runs (``-m "not slow"``) never pay for the subprocess.
    """
    try:
        p = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.device_count())"],
            capture_output=True, text=True, timeout=120, env=_env(devices))
        return int(p.stdout.strip()) if p.returncode == 0 else 0
    except (subprocess.SubprocessError, ValueError):
        return 0


def _require_devices() -> None:
    count = _forced_device_count()
    if count < REQUIRED_DEVICES:
        pytest.skip(f"host provides {count} < {REQUIRED_DEVICES} "
                    "(placeholder) jax devices")


def _run(code: str, devices: int = REQUIRED_DEVICES):
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=420,
                       env=_env(devices), cwd=REPO)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    return p.stdout


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    _require_devices()
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_forward

        S, M = 4, 8                     # 4 stages, 8 microbatches
        mesh = jax.make_mesh((S,), ("stage",))
        rng = np.random.default_rng(0)
        d = 16
        ws = jnp.asarray(rng.normal(size=(S, d, d)) / np.sqrt(d), jnp.float32)
        xs = jnp.asarray(rng.normal(size=(M * 2, d)), jnp.float32)

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        run = pipeline_forward(stage_fn, S, M, mesh, "stage")
        got = run(ws, xs)

        ref = xs
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        err = float(jnp.abs(got - ref).max())
        assert err < 1e-5, f"pipeline mismatch {err}"
        print("PIPELINE_OK", err)
    """)
    assert "PIPELINE_OK" in out


@pytest.mark.slow
def test_compressed_psum_across_real_pod_axis():
    _require_devices()
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed.collectives import compressed_psum

        mesh = jax.make_mesh((8,), ("pod",))
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)

        @partial(shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
                 out_specs=(P("pod"), P("pod")))
        def step(g, err):
            m, ne = compressed_psum(g[0], "pod", err[0])
            return m[None], ne[None]

        err = jnp.zeros_like(g_all)
        true_mean = np.asarray(g_all.mean(axis=0))
        # one-shot error <= int8 quantization bound; averaged over steps
        # with feedback it converges
        total = np.zeros(64)
        n = 30
        for _ in range(n):
            out, err = step(g_all, err)
            total += np.asarray(out[0])
        np.testing.assert_allclose(total / n, true_mean, atol=3e-3)
        print("COMPRESSED_OK")
    """)
    assert "COMPRESSED_OK" in out


@pytest.mark.slow
def test_elastic_remesh_checkpoint_restore():
    """A checkpoint written on an 8-device (4×2) mesh restores onto the
    6-device (3×2) mesh chosen by the failure planner after losing a host."""
    _require_devices()
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpoint import load_checkpoint, save_checkpoint
        from repro.checkpoint.failure import elastic_remesh

        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        w = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)
        tree = {"w": jax.device_put(
            w, NamedSharding(mesh8, P("data", "model")))}
        d = tempfile.mkdtemp()
        save_checkpoint(d, 5, tree)

        # lose one host (2 devices): planner keeps model axis = 2
        shape, idle = elastic_remesh(6, 2)
        assert shape == (3, 2) and idle == 0, (shape, idle)
        mesh6 = jax.make_mesh(shape, ("data", "model"))
        # 8 rows don't divide 3 -> restore replicated on data, sharded on model
        shardings = {"w": NamedSharding(mesh6, P(None, "model"))}
        restored, manifest = load_checkpoint(
            d, jax.eval_shape(lambda: {"w": w}), shardings=shardings)
        assert manifest["step"] == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
        assert restored["w"].sharding == shardings["w"]
        print("ELASTIC_OK")
    """, devices=8)
    assert "ELASTIC_OK" in out
