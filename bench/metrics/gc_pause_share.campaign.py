"""Share of the window in which Python's garbage collector held the
process (``gc_pause_seconds``, every generation)."""
from bench import program


def read(run):
    return program.window_share(run, "gc_pause_seconds")
