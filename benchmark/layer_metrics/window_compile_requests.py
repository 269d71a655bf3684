"""Compile requests (in-process jit misses) inside the window, counted
by jax.monitoring from outside the engine. Should read 0."""


def read(run):
    return run.jax_window.get("compile_requests")
