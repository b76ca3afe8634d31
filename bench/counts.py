"""Operations and bytes of the search loop's networks, counted from their
published widths (paper Fig. 2, Tables 5-6, Eqs. 54 and 69): two FLOPs per
multiply-add of every dense layer; a backward pass costs two forward
passes (gradients of inputs and of weights), or one where only input
gradients are needed.  Elementwise work is not counted.
"""
from __future__ import annotations

STATE, ACTION = 52, 30                  # SAC state and continuous action
HIDDEN, EXPERTS = 256, 4                # actor trunk width, MoE experts
HEAD_OUT = 4 * 5 + 2 * ACTION           # discrete logits, mean, log-std
CRITIC = (STATE + ACTION, 256, 256, 1)
WORLD_MODEL = (STATE + ACTION, 128, 64, STATE)
SURROGATE = (STATE + ACTION, 128, 64, 3)
SAC_BATCH, WM_BATCH, SUR_BATCH = 256, 256, 256
MPC_CANDIDATES, MPC_HORIZON = 64, 5
F32 = 4


def mlp_flops(rows: int, widths) -> float:
    return 2.0 * rows * sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def actor_flops(rows: int) -> float:
    """The MoE actor's forward: gate, and per expert two trunk layers and
    the three heads."""
    per_expert = STATE * HIDDEN + HIDDEN * HIDDEN + HIDDEN * HEAD_OUT
    return 2.0 * rows * (STATE * EXPERTS + EXPERTS * per_expert)


def actor_moe_bytes(rows: int) -> float:
    """HBM bytes the ``actor_moe`` kernel must move for ``rows`` states:
    the states in, every expert's weights once, the four outputs back."""
    weights = (STATE * EXPERTS + EXPERTS * (
        STATE * HIDDEN + HIDDEN + HIDDEN * HIDDEN + HIDDEN
        + HIDDEN * HEAD_OUT + HEAD_OUT))
    return F32 * (rows * STATE + weights + rows * (HEAD_OUT + EXPERTS))


def sac_update_flops(rows: int = SAC_BATCH) -> float:
    """One SAC step: the target policy and twin target critics forward,
    both critics forward and backward, the actor forward and backward with
    both new critics forward and backward to the actions."""
    critic = mlp_flops(rows, CRITIC)
    actor = actor_flops(rows)
    return (actor + 2 * critic          # targets
            + 2 * 3 * critic            # critic losses
            + 3 * actor + 2 * 2 * critic)  # actor loss


def mpc_flops(rows: int) -> float:
    """The MPC planner over ``rows`` states: one actor row each, then a
    rollout of every candidate through surrogate, world model and actor."""
    per_step = (mlp_flops(1, SURROGATE) + mlp_flops(1, WORLD_MODEL)
                + actor_flops(1))
    return rows * (actor_flops(1)
                   + MPC_CANDIDATES * MPC_HORIZON * per_step)


def learn_threshold(episodes: int, batch_size: int = SAC_BATCH,
                    warmup: int = 1000) -> int:
    """Replay size at which the loop starts learning."""
    return max(batch_size, min(warmup, episodes // 4))


def campaign_flops(dispatches: int, lanes: int, cells: int, episodes: int,
                   updates_per_dispatch: int = 4) -> float:
    """Network FLOPs of one cell batch's ``dispatches`` dispatches, MPC
    aside: every dispatch acts on all B lanes; from the dispatch whose
    replay reaches the threshold on, each learns (SAC updates, one world
    model and one surrogate step); from the one after, the surrogate also
    scores every lane for its calibration."""
    b = lanes * cells
    first_learn = -(-learn_threshold(episodes) // b) - 1
    learning = max(0, dispatches - first_learn)
    calib = max(0, dispatches - first_learn - 1)
    per_learn = (updates_per_dispatch * sac_update_flops()
                 + 3 * mlp_flops(WM_BATCH, WORLD_MODEL)
                 + 3 * mlp_flops(SUR_BATCH, SURROGATE))
    return (dispatches * actor_flops(b) + learning * per_learn
            + calib * mlp_flops(b, SURROGATE))
