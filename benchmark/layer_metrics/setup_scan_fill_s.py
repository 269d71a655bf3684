"""Set-up spent filling the scan cache: generating (or reading) table
lanes and pinning them on the device, the miss path of the scan cache
(``trino_tpu_scan_fill_seconds_sum`` at the window's start: process
start to window). The part of ``setup_first_pass_s`` and of the data
pins that is data, not compile."""

KEY = "trino_tpu_scan_fill_seconds_sum"


def read(run):
    return run.engine_before.get(KEY)
