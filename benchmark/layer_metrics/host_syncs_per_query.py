"""Times a query's thread waits for the device: blocking device-to-host
reads (``trino_tpu_host_reads_total``, every site) plus dispatched
programs waited for (count of ``device_execute`` spans: each ends in a
``block_until_ready``), per executed query. Each is a bubble on the
device."""

from ._phases import executed, family_growth, phase_count


def read(run):
    n = executed(run)
    reads = family_growth(run, "trino_tpu_host_reads_total")
    if n <= 0:
        return None
    return ((reads or 0.0) + phase_count(run, "device_execute")) / n
