"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _mk(shape, dtype):
    return jnp.asarray(RNG.normal(0, 1, shape), dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,H,Hk,S,hd,causal,window", [
    (1, 4, 2, 256, 64, True, 0),
    (2, 8, 8, 128, 128, True, 0),
    (1, 2, 1, 256, 64, False, 0),
    (1, 4, 4, 256, 64, True, 64),
    (2, 16, 4, 128, 64, True, 0),
])
def test_flash_attention_sweep(B, H, Hk, S, hd, causal, window, dtype, tol):
    q, k, v = (_mk((B, H, S, hd), dtype), _mk((B, Hk, S, hd), dtype),
               _mk((B, Hk, S, hd), dtype))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.attention_reference(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


@pytest.mark.parametrize("B,S,D,N,bd,ch", [
    (1, 128, 64, 8, 32, 64),
    (2, 256, 128, 16, 64, 128),
    (1, 64, 32, 4, 32, 32),
])
def test_ssm_scan_sweep(B, S, D, N, bd, ch):
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (B, S, D)), jnp.float32)
    b_in = _mk((B, S, N), jnp.float32)
    c_in = _mk((B, S, N), jnp.float32)
    x = _mk((B, S, D), jnp.float32)
    a = -jnp.exp(_mk((D, N), jnp.float32) * 0.5)
    y = ops.ssm_scan(dt, b_in, c_in, x, a, block_d=bd, chunk=ch,
                     interpret=True)
    want, _ = ref.ssm_scan_reference(dt, b_in, c_in, x, a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,block", [(64, 32), (300, 128), (16, 256)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)])
def test_fused_mlp_sweep(B, block, dtype, tol):
    d_in, h1, h2, d_out = 82, 128, 64, 52
    x = _mk((B, d_in), dtype)
    ws = [_mk((82, 128), jnp.float32) * 0.1, jnp.zeros(128),
          _mk((128, 64), jnp.float32) * 0.1, jnp.zeros(64),
          _mk((64, 52), jnp.float32) * 0.1, jnp.zeros(52)]
    y = ops.fused_mlp(x, *ws, block_b=block, interpret=True)
    want = ref.fused_mlp_reference(x, *ws)
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


# ------------------------------------------------------------------------
# DSE search-loop kernels (screen / MoE actor)
# ------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,block", [(8, 4, 8), (33, 6, 16), (128, 4, 64)])
def test_screen_scores_sweep(B, K, block):
    import jax

    from repro.core.actions import N_CONT
    from repro.core.state import SAC_STATE_DIM
    from repro.ppa.surrogate import init_params, screen_batch

    params = init_params(jax.random.PRNGKey(5), SAC_STATE_DIM + N_CONT)
    s = _mk((B, SAC_STATE_DIM), jnp.float32)
    cand = _mk((B, K, N_CONT), jnp.float32)
    w = jnp.asarray(RNG.dirichlet(np.ones(3), B), jnp.float32)
    score = ops.screen_scores(params, s, cand, w, block_b=block,
                              interpret=True)
    want = ref.screen_scores_reference(params, s, cand, w)
    np.testing.assert_allclose(np.asarray(score), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # full drop-in: same pick as the live surrogate screen on random
    # (well-separated) scores, gate open and closed
    mask = jnp.asarray(RNG.random(B) < 0.5)
    pick_k = ops.screen_batch(params, s, cand, w, mask, interpret=True)
    pick_r = screen_batch(params, s, cand, w, mask)
    assert bool(jnp.all(pick_k == pick_r))


@pytest.mark.parametrize("B", [4, 33, 256])
def test_actor_forward_parity(B):
    import jax

    from repro.core import networks as nets
    from repro.core.state import SAC_STATE_DIM

    params = nets.actor_init(jax.random.PRNGKey(3))
    s = _mk((B, SAC_STATE_DIM), jnp.float32)
    got = ops.actor_forward(params, s, interpret=True)
    want = ref.actor_forward_reference(params, s)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_policy_act_batch_parity():
    import jax

    from repro.core import networks as nets
    from repro.core import sac as sac_mod
    from repro.core.state import SAC_STATE_DIM

    params = nets.actor_init(jax.random.PRNGKey(4))
    s = _mk((64, SAC_STATE_DIM), jnp.float32)
    key = jax.random.PRNGKey(7)
    a_k, ad_k = ops.policy_act_batch(params, s, key, interpret=True)
    a_r, ad_r = sac_mod.policy_act_batch(params, s, key)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               rtol=1e-4, atol=1e-5)
    # categorical sampling sees float-eps logit differences; ties are
    # measure-zero on random logits but tolerate a stray flip
    assert float(jnp.mean(ad_k == ad_r)) >= 0.99


def test_chunked_attention_matches_kernel_layout():
    """Model-zoo chunked attention == kernel oracle (layout transposed)."""
    from repro.models.attention import chunked_attention
    B, S, H, Hk, hd = 2, 128, 4, 2, 64
    q = _mk((B, S, H, hd), jnp.float32)
    k = _mk((B, S, Hk, hd), jnp.float32)
    v = _mk((B, S, Hk, hd), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, q_chunk=32)
    want = ref.attention_reference(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               rtol=2e-4, atol=2e-4)


def test_kernels_enabled_follows_the_platform(monkeypatch):
    import jax

    assert ops.kernels_enabled() is (jax.default_backend() == "tpu")
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        ops.kernels_enabled()


def test_search_loop_runs_through_the_kernels(monkeypatch):
    """The path a TPU takes: the vec search loop with the actor and the
    screening kernels in place of the jnp reference (interpreted here),
    gate open, to the end of its budget."""
    from repro.configs import get_config
    from repro.core.search import SearchConfig, run_search_cells
    from repro.workload.extract import extract

    calls = {"act": 0, "screen": 0}

    def interpreted(name, fn):
        def call(*a, **k):
            calls[name] += 1
            return fn(*a, interpret=True, **k)
        return call

    monkeypatch.setattr(ops, "kernels_enabled", lambda: True)
    monkeypatch.setattr(ops, "policy_act_batch",
                        interpreted("act", ops.policy_act_batch))
    monkeypatch.setattr(ops, "screen_batch",
                        interpreted("screen", ops.screen_batch))
    wl = extract(get_config("smollm-135m"), seq_len=256, batch=1)
    sc = SearchConfig(episodes=48, warmup=16, batch_size=16, seed=0,
                      gate_threshold=1e9)
    (res,) = run_search_cells(wl, [7], search=sc, lanes_per_cell=8)
    assert res.episodes_run == 48
    assert res.gate_open_episode is not None
    assert res.screened > res.evaluated
    assert calls["act"] > 0 and calls["screen"] > 0
