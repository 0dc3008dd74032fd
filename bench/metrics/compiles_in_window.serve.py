"""XLA compilations (or compile-cache loads) inside the measured
window, from ``jax.monitoring``'s backend-compile events: should be 0."""


def read(ctx, trace):
    return float(ctx["compiles_in_window"])
