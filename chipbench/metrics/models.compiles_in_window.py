"""Programs compiled, or loaded from the persistent compilation cache,
while the window was open; JAX reports both through ``jax.monitoring``."""


def read(run):
    return float(sum(run.window.start <= t <= run.window.end
                     for t, _ in run.compiles))
