"""The ``actor_moe`` kernel's share of its roofline: the least time the
chip needs for the operations and bytes of its calls (counted from the
actor's widths in ``bench/counts.py``) over the kernel's device time in
the trace.  Read only when the trace holds one kernel call per dispatch,
all at the cell's batch."""
from bench import counts

KERNEL = "actor_forward"


def read(run):
    tr, c = run.trace, run.window.counts
    if tr is None or not run.peaks or "actor_rows" not in c:
        return None
    seconds, calls = tr.op_seconds(KERNEL)
    if calls == 0 or calls != c["dispatches"] or seconds <= 0:
        return None
    rows = c["actor_rows"]
    least = max(counts.actor_flops(rows) / run.peaks["bf16_flops"],
                counts.actor_moe_bytes(rows) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
