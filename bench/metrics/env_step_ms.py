"""Mean wall time of the search loop's ``env_step`` phase a dispatch (the
fused env step and its pulls), from
``search_phase_seconds{phase="env_step"}``."""
from bench import program


def read(run):
    return program.mean_ms(run, "search_phase_seconds", phase="env_step")
