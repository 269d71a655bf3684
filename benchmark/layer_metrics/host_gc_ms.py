"""Host runtime: milliseconds per query that the interpreter's garbage
collector held the process inside the window (gc.callbacks, from outside
the engine). Every thread waits while it runs."""


def read(run):
    n = sum(1 for r in run.records if r.ok)
    if not n or "pause_s" not in run.gc_window:
        return None
    return 1000.0 * run.gc_window["pause_s"] / n
