"""Process start to the first query of the window: imports, coordinator,
data pins, first executions (generation, compile or cache reads), warm
cycles."""


def read(run):
    return run.setup_s
