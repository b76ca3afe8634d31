"""The search loop's network FLOPs in the window (counted from widths in
``bench/counts.py``: policy act, SAC, world model and surrogate steps;
MPC planning where the trace shows its program) over the window times
the chip's bf16 peak."""
from bench import counts

MPC_PROGRAM = "plan_batch"


def read(run):
    tr, c = run.trace, run.window.counts
    if tr is None or not run.peaks or "flops" not in c:
        return None
    _, mpc_calls = tr.module_runs(MPC_PROGRAM)
    flops = c["flops"] + mpc_calls * counts.mpc_flops(c["actor_rows"])
    return 100.0 * flops / (tr.window_s * run.peaks["bf16_flops"])
