"""``compiles_in_window``: programs compiled, or loaded from the
persistent cache, while the measured window ran (``jax.monitoring``
events); every program should be ready before it starts."""


def read(run):
    return float(run.compiles)
