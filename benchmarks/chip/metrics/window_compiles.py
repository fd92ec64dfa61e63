"""Compilation (jax.jit and the eager prefill path): XLA compiles and
persistent-cache loads started inside the window, counted from JAX's
backend-compile events."""


def read(r):
    return r.rec.compiles
