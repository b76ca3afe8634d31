"""Share of the window spent writing search checkpoints (program spans
``checkpoint``)."""
from bench import intervals as iv


def read(run):
    w = run.window
    ckpt = [(s["ts"], s["ts"] + s["dur"]) for s in w.spans
            if s["name"] == "checkpoint"]
    if not ckpt:
        return None
    return 100.0 * iv.covered(ckpt, w.t0, w.t1) / w.seconds
