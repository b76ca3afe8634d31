"""Mean wall time of the search loop's ``act`` phase a dispatch (policy
act, MPC blend, screening, eps-greedy and the pulls of the actions), from
``search_phase_seconds{phase="act"}``."""
from bench import program


def read(run):
    return program.mean_ms(run, "search_phase_seconds", phase="act")
