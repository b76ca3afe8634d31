"""Mean wall time of one search-loop dispatch in the window, from the
program's ``dispatch_seconds`` histogram (each ends once the host has the
dispatch's results)."""


def read(run):
    h = run.registry["histograms"].get(("dispatch_seconds", ()))
    if not h or h["count"] <= 0:
        return None
    return 1e3 * h["sum"] / h["count"]
