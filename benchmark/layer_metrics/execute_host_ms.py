"""Python between programs: the ``execute`` root span minus the spans
under it in which the thread waits or fills (``device_execute``,
``host_read``, ``scan_fill``, ``jit_trace``), per executed query. A
fill or a first trace holds dispatches and reads of its own, so in a
window that compiles or fills this reads low (``window_compile_requests``
and ``scan_cache_hit_pct`` say when)."""

from ._phases import executed, phase_seconds


def read(run):
    n = executed(run)
    if n <= 0:
        return None
    inside = phase_seconds(run, "device_execute", "host_read",
                           "scan_fill", "jit_trace")
    return 1e3 * (phase_seconds(run, "execute") - inside) / n
