"""Tests: data pipeline, checkpointing, fault tolerance, compressed
collectives, pipeline parallelism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.checkpoint import (CheckpointManager, latest_step,
                                         load_checkpoint, save_checkpoint)
from repro.checkpoint.failure import (ElasticPlan, FailureManager,
                                      StragglerPolicy, elastic_remesh)
from repro.data.pipeline import DataConfig, TokenPipeline


# ------------------------------------------------------------------ data


def test_pipeline_deterministic_and_seekable():
    cfg = DataConfig(vocab_size=101, seq_len=16, global_batch=4, seed=7)
    p1 = TokenPipeline(cfg)
    p2 = TokenPipeline(cfg)
    b5a = p1.batch_at(5)
    b5b = p2.batch_at(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert not np.array_equal(p1.batch_at(6)["tokens"], b5a["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b5a["tokens"][:, 1:], b5a["labels"][:, :-1])


def test_pipeline_prefetch_matches_direct():
    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2, seed=1)
    p = TokenPipeline(cfg)
    p.start(cursor=3)
    idx, batch = next(p)
    assert idx == 3
    np.testing.assert_array_equal(batch["tokens"], p.batch_at(3)["tokens"])
    idx2, _ = next(p)
    assert idx2 == 4
    p.stop()


def test_pipeline_host_sharding():
    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=8, seed=1)
    shards = [TokenPipeline(cfg, host_index=i, host_count=4) for i in range(4)]
    batches = [s.batch_at(0)["tokens"] for s in shards]
    assert all(b.shape == (2, 8) for b in batches)
    # host shards differ (independent slices of the global batch)
    assert not np.array_equal(batches[0], batches[1])


def test_pipeline_learnable_structure():
    """The Markov overlay must make next-token prediction beat chance."""
    cfg = DataConfig(vocab_size=50, seq_len=256, global_batch=8, seed=0,
                     markov_strength=0.9)
    p = TokenPipeline(cfg)
    b = p.batch_at(0)
    follows = (p._perm[b["tokens"]] == b["labels"]).mean()
    assert follows > 0.5  # most transitions follow the permutation


# ------------------------------------------------------------------ checkpoint


def tree_example(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "b": jnp.zeros((16,), jnp.bfloat16)},
        "m": {"w": jnp.ones((8, 16)), "b": jnp.zeros((16,), jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip_bit_exact(tmp_path):
    tree = tree_example()
    save_checkpoint(str(tmp_path), 7, tree, {"note": "hi"})
    template = jax.eval_shape(lambda: tree)
    restored, manifest = load_checkpoint(str(tmp_path), template)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_checkpoint_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, save_every=10)
    tree = tree_example()
    for step in (10, 20, 30):
        mgr.save(step, tree, async_=False)
    assert mgr.latest_step() == 30
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000020", "step_00000030"]
    assert mgr.should_save(10) and not mgr.should_save(11)


def test_checkpoint_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, save_every=1)
    mgr.save(5, tree_example(), async_=True)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 5


def test_checkpoint_resharding_load(tmp_path):
    """A checkpoint saved unsharded restores onto an explicit sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 1, tree)
    mesh = jax.make_mesh((1,), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = load_checkpoint(str(tmp_path), jax.eval_shape(lambda: tree),
                                  shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


@pytest.mark.slow
def test_train_restart_bit_exact(tmp_path):
    """Kill a training run mid-stream; resume; final state must be bit-exact
    equal to an uninterrupted run (fault-tolerance integration test)."""
    from repro.launch.train import main as train_main

    common = ["--arch", "xlstm-125m", "--smoke", "--batch", "2", "--seq", "32",
              "--steps", "6", "--ckpt-every", "2", "--log-every", "100"]
    d1 = str(tmp_path / "interrupted")
    out1 = train_main(common + ["--ckpt-dir", d1, "--stop-after", "3"])
    assert out1["steps_run"] == 3
    out2 = train_main(common + ["--ckpt-dir", d1])  # resume
    assert out2["resumed_from"] == 2  # last checkpoint before the failure
    d2 = str(tmp_path / "clean")
    out3 = train_main(common + ["--ckpt-dir", d2])
    assert out3["steps_run"] == 6

    t1, m1 = load_checkpoint(d1, None) if False else (None, None)
    from repro.checkpoint.checkpoint import load_checkpoint as lc
    import jax
    # compare final checkpoints bit-exactly
    with open(os.path.join(d1, "step_00000006", "manifest.json")) as f:
        pass
    tree1, man1 = _load_raw(d1, 6)
    tree2, man2 = _load_raw(d2, 6)
    assert set(tree1) == set(tree2)
    for k in tree1:
        np.testing.assert_array_equal(tree1[k], tree2[k], err_msg=k)


def _load_raw(directory, step):
    import json
    import msgpack

    from repro.checkpoint.checkpoint import decompress_payload

    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(path, "arrays.msgpack.zst"), "rb") as f:
        raw = decompress_payload(f.read(), manifest.get("codec", "zstd"))
    payload = msgpack.unpackb(raw, raw=False)
    out = {}
    for key, info in manifest["arrays"].items():
        out[key] = np.frombuffer(payload[key], np.dtype(info["dtype"])) \
            .reshape(info["shape"])
    return out, manifest


# ------------------------------------------------------------------ failure


def test_elastic_remesh_preserves_model_axis():
    shape, idle = elastic_remesh(256, 16)
    assert shape == (16, 16) and idle == 0
    # lose one 8-device host: 248 devices -> 15x16 used, 8 idle
    shape, idle = elastic_remesh(248, 16)
    assert shape == (15, 16) and idle == 8
    with pytest.raises(ValueError):
        elastic_remesh(8, 16)


def test_failure_manager_detects_and_plans():
    fm = FailureManager(hosts=range(4), devices_per_host=64, model_axis=16,
                        timeout=10.0)
    now = 1000.0
    for h in range(4):
        fm.heartbeat(h, now)
    assert fm.check(now + 5) == []
    fm.heartbeat(0, now + 8)
    fm.heartbeat(1, now + 8)
    fm.heartbeat(2, now + 8)
    dead = fm.check(now + 12)
    assert dead == [3]
    plan = fm.plan(resume_step=120)
    assert plan.dropped_hosts == (3,)
    assert plan.devices_used == 192  # 3 hosts × 64, 12×16 mesh
    assert plan.mesh_shape == (12, 16)
    assert plan.resume_step == 120
    # rejoin
    fm.admit(3, now + 20)
    assert 3 in fm.alive


def test_straggler_policy_escalates():
    sp = StragglerPolicy(deadline_s=1.0, misses_to_fail=3, window=5)
    assert not sp.observe(0, 0.5)
    assert not sp.observe(0, 2.0)
    assert not sp.observe(0, 2.0)
    assert sp.observe(0, 2.0)  # third miss
    sp.reset(0)
    assert not sp.observe(0, 2.0)


# ------------------------------------------------------------------ collectives


def test_quantize_roundtrip_exact_for_representable():
    from repro.distributed.collectives import dequantize_int8, quantize_int8

    # values that are integer multiples of the scale roundtrip exactly
    x = jnp.asarray([0.0, 127.0, -127.0, 64.0, 32.0])
    q, s = quantize_int8(x)
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, s)),
                               np.asarray(x), rtol=1e-6)


@pytest.mark.slow
def test_compressed_psum_error_feedback_converges():
    """Mean of a constant gradient over repeated steps: error feedback makes
    the time-averaged compressed mean converge to the true mean."""
    from jax import shard_map
    from repro.distributed.collectives import compressed_psum

    mesh = jax.make_mesh((1,), ("pod",))
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)

    from functools import partial

    @partial(shard_map, mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),
                                             jax.sharding.PartitionSpec()),
             out_specs=(jax.sharding.PartitionSpec(),
                        jax.sharding.PartitionSpec()))
    def step(x, err):
        return compressed_psum(x, "pod", err)

    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    n = 50
    for _ in range(n):
        out, err = step(g, err)
        total = total + out
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(g),
                               atol=2e-3)


def test_compressed_grad_sync_tree():
    from repro.distributed.collectives import compressed_grad_sync

    mesh = jax.make_mesh((1,), ("pod",))
    grads = {"a": jnp.ones((4, 4)), "b": {"c": jnp.full((3,), -2.0)}}
    out, errs = compressed_grad_sync(grads, None, mesh)
    for k, v in [("a", 1.0)]:
        np.testing.assert_allclose(np.asarray(out["a"]), 1.0, atol=1e-2)
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), -2.0, atol=2e-2)
    assert jax.tree.structure(errs) == jax.tree.structure(grads)


# ------------------------------------------------------------------ pipeline PP


def test_pipeline_forward_matches_sequential():
    pytest.importorskip("jax")
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs >=2 devices for a stage axis")


def test_pipeline_bubble_fraction():
    from repro.distributed.pipeline import bubble_fraction

    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
