"""Mean time a recommend request holds the server's lock: its
``recommend_batch``, exact lookups and fallback dispatch
(``serve_lock_hold_seconds``)."""
from bench import program


def read(run):
    return program.mean_ms(run, "serve_lock_hold_seconds")
