"""Seconds of backend compiles and persistent-cache reads before the
window (jax.monitoring's backend_compile_duration covers both)."""


def read(run):
    return run.jax_setup.get("compile_s")
