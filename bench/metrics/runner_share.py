"""Share of the window outside the search loop (program spans
``run_search_cells``): campaign planning, store writes, reports and the
hand-over between campaigns."""
from bench import intervals as iv


def read(run):
    w = run.window
    loops = [(s["ts"], s["ts"] + s["dur"]) for s in w.spans
             if s["name"] == "run_search_cells"]
    if not loops:
        return None
    return 100.0 * (1.0 - iv.covered(loops, w.t0, w.t1) / w.seconds)
