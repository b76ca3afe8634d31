"""Mean wall time of the search loop's ``archive`` phase a dispatch (PER
add, surrogate buffers, best tracking, the per-lane Pareto insert,
``seen``, gate accounting and calibration), from
``search_phase_seconds{phase="archive"}``."""
from bench import program


def read(run):
    return program.mean_ms(run, "search_phase_seconds", phase="archive")
