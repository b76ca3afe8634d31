"""Share of the traced window's device-idle time whose innermost program
annotation on the profile is a leaf (a phase, a span that holds no
other, a GC pause); the rest lies under a container alone (a dispatch,
``run_batch``, ``run_search_cells``) or under no program annotation."""
from bench import program


def read(run):
    tr = run.trace
    if tr is None:
        return None
    idle = program.idle_by_name(tr)
    if not idle:
        return None
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (total - idle.get(None, 0.0)) / total
