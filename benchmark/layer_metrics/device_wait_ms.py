"""The query thread blocked on the chip: ``device_execute`` spans
(dispatch of a cached program to its outputs ready) plus ``host_read``
spans (a blocking device-to-host read between programs), per executed
query. An upper bound on device time taken on the host's clock."""

from ._phases import per_query_ms


def read(run):
    return per_query_ms(run, "device_execute", "host_read")
