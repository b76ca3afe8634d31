"""Mean time the recommend server spends on a request, from its
``serve_request_seconds`` histogram."""


def read(run):
    h = run.registry["histograms"].get(("serve_request_seconds", ()))
    if not h or h["count"] <= 0:
        return None
    return 1e3 * h["sum"] / h["count"]
