"""Programs JAX compiled (or fetched from its cache) inside the measured
window, counted by a `jax.monitoring` listener; expected 0."""


def read(ctx):
    return ctx["counters"]["window_compiles"]
