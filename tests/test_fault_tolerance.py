"""Checkpoint/restart, preemption simulation, elastic restore, data
determinism, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.data.pipeline import DataConfig, batch_at
from repro.distributed.compression import (compress, compressed_psum,
                                           decompress, init_residuals)


def tree_allclose(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), rtol=1e-6)


def test_checkpoint_roundtrip_atomic(tmp_path):
    tree = dict(a=jnp.arange(10, dtype=jnp.float32),
                b=dict(c=jnp.ones((3, 4), jnp.bfloat16),
                       d=jnp.asarray(3, jnp.int32)))
    path = ckpt.save(tree, str(tmp_path), 7)
    assert os.path.basename(path) == "step_00000007"
    back = ckpt.restore(tree, str(tmp_path))
    tree_allclose(tree, back)
    assert back["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_retention_and_latest(tmp_path):
    tree = dict(x=jnp.zeros(4))
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(tree, str(tmp_path), s, keep=3)
    assert ckpt.all_steps(str(tmp_path)) == [3, 4, 5]
    assert ckpt.latest_step(str(tmp_path)) == 5


@pytest.mark.slow
def test_preemption_resume_bit_exact(tmp_path):
    """Train 6 steps straight vs 3 steps -> kill -> resume 3: identical."""
    from repro.launch.train import train
    d1 = str(tmp_path / "a")
    d2 = str(tmp_path / "b")
    s_full, l_full = train("smollm-135m", reduced=True, steps=6,
                           global_batch=4, seq_len=32, ckpt_dir=d1,
                           ckpt_every=3, log_every=100)
    train("smollm-135m", reduced=True, steps=6, global_batch=4, seq_len=32,
          ckpt_dir=d2, ckpt_every=3, stop_after=3, log_every=100)
    s_res, l_res = train("smollm-135m", reduced=True, steps=6,
                         global_batch=4, seq_len=32, ckpt_dir=d2,
                         ckpt_every=3, resume="auto", log_every=100)
    tree_allclose(s_full.params, s_res.params)
    assert int(s_full.step) == int(s_res.step) == 6


def test_elastic_restore_other_mesh(tmp_path):
    """Save on 1-device layout, restore onto a different (sharded) mesh."""
    from repro.launch.mesh import make_test_mesh
    from repro.distributed import sharding as sh
    tree = dict(w=jnp.arange(64, dtype=jnp.float32).reshape(8, 8))
    ckpt.save(tree, str(tmp_path), 1)
    mesh = make_test_mesh(1, 1)
    shard = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data", "model")), tree)
    back = ckpt.restore(tree, str(tmp_path), shardings=shard)
    tree_allclose(tree, back)


def test_data_pipeline_deterministic_and_shardable():
    dc = DataConfig(vocab=101, seq_len=16, global_batch=8, seed=3)
    b1 = batch_at(dc, 5)
    b2 = batch_at(dc, 5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # shard-addressable: 2 shards reproduce independently + labels shift
    dcs = [DataConfig(vocab=101, seq_len=16, global_batch=8, seed=3,
                      n_shards=2, shard=i) for i in range(2)]
    s0, s1 = batch_at(dcs[0], 5), batch_at(dcs[1], 5)
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(s0["tokens"], s1["tokens"])
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_compression_error_feedback_converges():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (1000,)), jnp.float32)
    resid = jnp.zeros_like(x)
    acc = jnp.zeros_like(x)
    for _ in range(50):
        q, scale, resid = compress(x, resid)
        acc = acc + decompress(q, scale)
    # mean of the 50 decompressed payloads -> x (EF removes bias)
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(x),
                               atol=2e-2)


def test_compressed_psum_shard_map():
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    g = dict(w=jnp.ones((8,), jnp.float32) * 3.0)
    r = init_residuals(g)

    def f(g, r):
        return compressed_psum(g, r, "dp")

    out, new_r = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()))(g, r)
    np.testing.assert_allclose(np.asarray(out["w"]), 3.0, atol=0.05)
