"""Multi-device vec engine parity: the sharded fused step and the sharded
search loop must be BITWISE identical to the single-device run at equal
batch — sharding is an execution layout, not a numerics change.

Mesh sizes above ``jax.device_count()`` are skipped; CI's ``multidev``
step runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
so the {2, 4}-device cases execute there.  Emulate locally the same way
(the flag must be set before jax imports).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import actions as act
from repro.core.env import VecDSEEnv
from repro.core.search import SearchConfig, run_search_cells
from repro.distributed.sharding import batch_mesh, shard_keys
from repro.workload.extract import extract


@pytest.fixture(scope="module")
def wl():
    return extract(get_config("smollm-135m"), seq_len=2048, batch=3)


def _needs(n: int):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()} "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_count"
                    f"={n})")


# ----------------------------------------------------------------- mesh --
def test_batch_mesh_degenerate_and_oversubscribed():
    mesh = batch_mesh(1)
    assert mesh.devices.size == 1 and mesh.axis_names == ("batch",)
    with pytest.raises(ValueError, match="visible"):
        batch_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        batch_mesh(0)


def test_shard_keys_independent_and_deterministic():
    key = jax.random.PRNGKey(123)
    ks = shard_keys(key, 8)
    assert ks.shape[0] == 8
    # all streams distinct (fold_in of distinct shard ids)
    raw = np.asarray(jax.random.key_data(ks))
    assert len({tuple(r) for r in raw}) == 8
    # deterministic in the global seed, and a prefix of a larger deal
    again = np.asarray(jax.random.key_data(shard_keys(key, 8)))
    np.testing.assert_array_equal(raw, again)
    wider = np.asarray(jax.random.key_data(shard_keys(key, 16)))[:8]
    np.testing.assert_array_equal(raw, wider)
    # draws from distinct streams are uncorrelated draws, not copies
    draws = jax.vmap(lambda k: jax.random.normal(k, (4,)))(ks)
    assert len({tuple(np.asarray(d)) for d in draws}) == 8


def test_env_rejects_indivisible_batch(wl):
    with pytest.raises(ValueError, match="divide evenly"):
        VecDSEEnv(wl, 7, batch=15, seed=0, devices=4)


# ------------------------------------------------------- env step parity --
def _rollout(wl, devices, batch=16, steps=5):
    env = VecDSEEnv(wl, 7, batch=batch, seed=0, devices=devices)
    obs = [env.reset()]
    rng = np.random.default_rng(0)
    rs, mets = [], []
    for _ in range(steps):
        a_c, a_d = act.random_action_batch(rng, batch)
        o, r, info = env.step(a_c, a_d)
        obs.append(o)
        rs.append(r)
        mets.append(info.metrics)
    return (np.stack(obs), np.stack(rs), np.stack(mets))


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_env_step_bitwise_vs_single_device(wl, n_dev):
    _needs(n_dev)
    base = _rollout(wl, None)
    shard = _rollout(wl, n_dev)
    for name, a, b in zip(("obs", "reward", "metrics"), base, shard):
        np.testing.assert_array_equal(a, b, err_msg=name)


# --------------------------------------------------- search loop parity --
def _search(wl, devices):
    sc = SearchConfig(episodes=48, warmup=24, batch_size=32, seed=0)
    return run_search_cells(wl, [7, 7], search=sc, lanes_per_cell=4,
                            devices=devices)


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_search_cells_bitwise_vs_single_device(wl, n_dev):
    _needs(n_dev)
    base = _search(wl, None)
    shard = _search(wl, n_dev)
    assert len(base) == len(shard)
    for rb, rs in zip(base, shard):
        assert rb.episodes_run == rs.episodes_run
        assert rb.feasible_count == rs.feasible_count
        assert rb.unique_configs == rs.unique_configs
        # bitwise: float equality, no tolerance
        assert rb.best_score == rs.best_score
        if rb.best_cfg is None:
            assert rs.best_cfg is None
        else:
            np.testing.assert_array_equal(rb.best_cfg, rs.best_cfg)
        fb, fs = rb.archive.frontier(), rs.archive.frontier()
        assert sorted(fb) == sorted(fs)
        for k in fb:
            np.testing.assert_array_equal(fb[k], fs[k])


# ------------------------------------------------ kernel interpret modes --
def test_kernel_interpret_paths_match_references():
    """The two search-loop Pallas kernels execute (interpret mode) and
    match their jnp/host references — the cheap cross-check the dedicated
    ``tests/test_kernels.py`` sweeps expand on."""
    from repro.core import networks as nets
    from repro.core import sac as sac_mod
    from repro.core.state import SAC_STATE_DIM
    from repro.kernels import ops, ref
    from repro.ppa import surrogate as sur_mod
    from repro.core.actions import N_CONT

    rng = np.random.default_rng(0)
    B, K = 16, 4
    s = jnp.asarray(rng.normal(0, 1, (B, SAC_STATE_DIM)), jnp.float32)

    sp = sur_mod.init_params(jax.random.PRNGKey(1), SAC_STATE_DIM + N_CONT)
    cand = jnp.asarray(rng.normal(0, 1, (B, K, N_CONT)), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(3), B), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.screen_scores(sp, s, cand, w, interpret=True)),
        np.asarray(ref.screen_scores_reference(sp, s, cand, w)),
        rtol=1e-4, atol=1e-5)

    ap = nets.actor_init(jax.random.PRNGKey(2))
    a_k, ad_k = ops.policy_act_batch(ap, s, jax.random.PRNGKey(3),
                                     interpret=True)
    a_r, ad_r = sac_mod.policy_act_batch(ap, s, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.mean(ad_k == ad_r)) >= 0.99

