"""The benchmark's operation and byte counters against hand counts and
against XLA's own count of the same forward passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts


def test_mlp_flops_hand_count():
    # 3 rows through 4 -> 5 -> 2: 2 * 3 * (4*5 + 5*2)
    assert counts.mlp_flops(3, (4, 5, 2)) == 2 * 3 * (20 + 10)


def test_actor_flops_hand_count():
    # per row: gate 52x4, and per expert 52x256, 256x256, 256x80
    per_row = 52 * 4 + 4 * (52 * 256 + 256 * 256 + 256 * 80)
    assert counts.actor_flops(1) == 2 * per_row
    assert counts.actor_flops(448) == 448 * 2 * per_row


def test_actor_moe_bytes_hand_count():
    weights = 52 * 4 + 4 * (52 * 256 + 256 + 256 * 256 + 256 + 256 * 80
                            + 80)
    assert counts.actor_moe_bytes(8) == 4 * (8 * 52 + weights + 8 * 84)


def test_sac_update_flops_hand_count():
    c, a = counts.mlp_flops(256, counts.CRITIC), counts.actor_flops(256)
    assert counts.sac_update_flops() == (a + 2 * c) + 6 * c + (3 * a + 4 * c)


def test_campaign_flops_learning_starts_at_the_replay_threshold():
    b = 7 * 64
    act = counts.actor_flops(b)
    # 1000 rows reached in the third dispatch: two dispatches only act
    assert counts.campaign_flops(2, 64, 7, 4613) == 2 * act
    third = counts.campaign_flops(3, 64, 7, 4613) - 3 * act
    assert third == pytest.approx(
        4 * counts.sac_update_flops()
        + 3 * counts.mlp_flops(256, counts.WORLD_MODEL)
        + 3 * counts.mlp_flops(256, counts.SURROGATE))


def _xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_actor_flops_match_xla_count_of_the_plain_forward():
    """XLA's count of the jnp actor forward covers the same products plus
    its elementwise work, so it lies a little above the counter."""
    from bench.reference import nets
    rows = 16
    rng = np.random.default_rng(0)
    k, s, h, o = counts.EXPERTS, counts.STATE, counts.HIDDEN, counts.HEAD_OUT
    w = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)

    def forward(x, gate, w1, w2, w3):
        g = jax.nn.softmax(x @ gate, axis=-1)
        h1 = jax.nn.gelu(jnp.einsum("bs,kso->bko", x, w1))
        h2 = jax.nn.gelu(jnp.einsum("bkh,kho->bko", h1, w2))
        return jnp.einsum("bk,bko->bo", g, jnp.einsum("bkh,kho->bko", h2,
                                                      w3))
    xla = _xla_flops(forward, w(rows, s), w(s, k), w(k, s, h), w(k, h, h),
                     w(k, h, o))
    ours = counts.actor_flops(rows)
    assert ours <= xla <= 1.1 * ours
    assert nets.HEADS == ("disc", "mu", "log_std")
