"""Share of the search loop's dispatch time (``dispatch_seconds``) that
none of its phases (``search_phase_seconds``: act, env_step, archive,
learn) accounts for."""
from bench import program

PHASES = ("act", "env_step", "archive", "learn")


def read(run):
    disp = program.hist(run, "dispatch_seconds")
    phases = [program.hist(run, "search_phase_seconds", phase=p)
              for p in PHASES]
    if disp is None or disp["sum"] <= 0 or None in phases:
        return None
    return 100.0 * (disp["sum"] - sum(h["sum"] for h in phases)) / disp["sum"]
