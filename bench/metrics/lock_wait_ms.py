"""Mean time a recommend request waits for the server's lock, from asking
for it until it is held (``serve_lock_wait_seconds``)."""
from bench import program


def read(run):
    return program.mean_ms(run, "serve_lock_wait_seconds")
