"""Share of the window's env steps whose design breaks the WMEM capacity
constraint (Eq. 14: the weights do not fit the die's ROM), from the
program's ``env_infeasible_total{reason="wmem"}`` over ``env_steps_total``,
in %.  None where the program has no such counter."""


def read(run):
    counters = run.registry["counters"]
    wmem = counters.get(("env_infeasible_total", (("reason", "wmem"),)))
    steps = counters.get(("env_steps_total", ()))
    if wmem is None or not steps:
        return None
    return 100.0 * wmem / steps
