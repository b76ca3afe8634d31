"""Compile the search loop's device work for one TPU v5e chip.

The TPU compiler is installed with jax, and it compiles for a chip that
is described but not attached, so these tests show, without a chip,
that the Pallas search kernels lower natively (``interpret=False``) and
that the fused env step compiles at the widths the chip smoke runs
(``chip_smoke.py``: 7 cells x 64 lanes = 448).  Nothing runs: a pass
here says the chip's compiler accepts the program, not that its results
are right.

The topology is described inside a module-scoped fixture, so only the
test process that is handed this file loads the TPU library; the
persistent compile cache is off around the compiles, since an entry
compiled for a described chip cannot be read back without one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import actions as act
from repro.core import networks as nets
from repro.core.state import SAC_STATE_DIM
from repro.kernels.actor_moe import actor_forward_pallas
from repro.kernels.screen_score import screen_scores_pallas
from repro.ppa import surrogate as sur_mod

SMOKE_B = 448          # chip_smoke.py: 7 nodes x 64 lanes per dispatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B", [SMOKE_B, 4096])
def test_actor_moe_compiles_for_v5e(one_chip, B):
    p = nets.actor_init(jax.random.PRNGKey(0))
    leaves = (p["gate"], p["l1"]["w"], p["l1"]["b"], p["l2"]["w"],
              p["l2"]["b"], p["disc"]["w"], p["disc"]["b"], p["mu"]["w"],
              p["mu"]["b"], p["log_std"]["w"], p["log_std"]["b"])
    s = np.zeros((B, SAC_STATE_DIM), np.float32)
    text = _compiled_text(
        lambda s, *w: actor_forward_pallas(s, *w, interpret=False),
        *_spec((s,) + leaves, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [SMOKE_B, 4096])
def test_screen_score_compiles_for_v5e(one_chip, B):
    K = 4
    p = sur_mod.init_params(jax.random.PRNGKey(0),
                            SAC_STATE_DIM + act.N_CONT)
    args = (np.zeros((B, SAC_STATE_DIM), np.float32),
            np.zeros((B, K, act.N_CONT), np.float32),
            np.zeros((B, 3), np.float32),
            p["l1"]["w"], p["l1"]["b"], p["l2"]["w"], p["l2"]["b"],
            p["head"]["w"], p["head"]["b"])
    text = _compiled_text(
        lambda *a: screen_scores_pallas(*a, interpret=False),
        *_spec(args, one_chip))
    assert "tpu_custom_call" in text


def test_fused_env_step_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.core.env import VecDSEEnv, _step_analytic_fn
    from repro.ppa.nodes import NODES
    from repro.workload.extract import extract

    wl = extract(get_config("smolvlm"), seq_len=512, batch=1)
    nodes = np.repeat(NODES, SMOKE_B // len(NODES)).tolist()
    env = VecDSEEnv(wl, nodes, high_perf=False)
    delta = act.cont_delta(np.zeros((SMOKE_B, act.N_CONT), np.float32))
    a_d = np.zeros((SMOKE_B, act.N_DISC), np.int32)
    args = (env.cfg, delta, a_d, env.wl_vec, env.node_mat, env.ranges,
            env.weights)
    compiled = jax.jit(_step_analytic_fn).lower(
        *_spec(args, one_chip)).compile()
    assert compiled.memory_analysis() is not None
