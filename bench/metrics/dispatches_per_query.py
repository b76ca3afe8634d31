"""Fused surrogate dispatches per query answered, from the server's
``serve_fused_dispatches_total`` counter."""


def read(run):
    n = run.window.counts.get("queries", 0)
    d = run.registry["counters"].get(("serve_fused_dispatches_total", ()))
    if not n or d is None:
        return None
    return d / n
