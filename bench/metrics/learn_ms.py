"""Mean wall time of the search loop's ``learn`` phase a dispatch (the SAC
updates on host-sampled PER batches with their priority updates, the
world-model step and the periodic surrogate fit), from
``search_phase_seconds{phase="learn"}``."""
from bench import program


def read(run):
    return program.mean_ms(run, "search_phase_seconds", phase="learn")
