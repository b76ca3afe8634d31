"""Backend compiles inside the measured window (``jax.monitoring``)."""


def read(run):
    return float(run.compiles[0])
