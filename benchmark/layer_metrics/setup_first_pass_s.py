"""The first execution of each query class of the cell: on-device
generation of the lanes it reads, tracing, compile or cache reads, and
the first run. Benchmark's own span; the split between generation and
the rest needs a span inside the program."""


def read(run):
    return run.phases.get("first_pass")
