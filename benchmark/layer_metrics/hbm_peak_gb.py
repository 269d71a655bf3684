"""Device: peak bytes in use on the fullest chip after the window."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
