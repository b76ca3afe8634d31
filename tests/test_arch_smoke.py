"""Per-architecture smoke tests: REDUCED configs, one forward + one real
train step on CPU, asserting output shapes and finiteness (assignment
requirement), plus prefill->decode cache consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import layers as L
from repro.models import lm
from repro.optim.trainer import TrainConfig, create_state, make_train_step

# tier-1 runs a small dense + MoE representative pair and the MoE + MLA
# arch with a leading dense layer; the full zoo rides in the slow tier
# (same assertions, just heavier reduced configs)
FAST_ARCHS = ("smollm-135m", "mixtral-8x7b", "deepseek-v2-lite")
ASSIGNED = [a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
            for a in ARCH_IDS]


def _inputs(cfg, B=2, S=16, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                                cfg.vocab)
    ctx = None
    if cfg.n_context_tokens or cfg.is_encdec:
        n = cfg.n_audio_frames if cfg.is_encdec else cfg.n_context_tokens
        ctx = (jax.random.normal(key, (B, n, cfg.d_model)) * 0.1).astype(
            L.dtype_of(cfg.param_dtype))
    return tokens, labels, ctx


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_shapes_no_nan(arch):
    cfg = get_reduced(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, labels, ctx = _inputs(cfg)
    logits = lm.forward(params, cfg, tokens, ctx)
    assert logits.shape == (2, 16, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_runs_and_is_finite(arch):
    cfg = get_reduced(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    state = create_state(params)
    tc = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = jax.jit(make_train_step(cfg, tc))
    tokens, labels, ctx = _inputs(cfg)
    batch = dict(tokens=tokens, labels=labels)
    if ctx is not None:
        batch["ctx"] = ctx
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state.step) == 1
    # params actually changed (max over all leaves; single leaves can be
    # bf16-rounding-stationary after one step)
    delta = max(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
                for a, b in zip(jax.tree.leaves(state.params),
                                jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_forward(arch):
    cfg = get_reduced(arch)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    B, S = 2, 12
    tokens, _, ctx = _inputs(cfg, B, S, seed=1)
    full = lm.forward(params, cfg, tokens, ctx)
    _, caches = lm.prefill(params, cfg, tokens[:, :S - 1], ctx)
    caches = lm.extend_caches(caches, cfg, S + 4)
    lg, _ = lm.decode_step(params, cfg, tokens[:, S - 1:S], caches,
                           jnp.asarray(S - 1))
    a = np.asarray(full[:, -1], np.float32)
    b = np.asarray(lg[:, -1], np.float32)
    err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert err < 0.05, err


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_count_matches_analytic(arch):
    """init_params leaf totals ~= ArchConfig.param_counts() (5%)."""
    cfg = get_reduced(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    want = cfg.param_counts()["total"]
    assert abs(n - want) / want < 0.08, (n, want)
