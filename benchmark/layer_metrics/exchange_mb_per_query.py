"""Exchange: megabytes of live rows the mesh executor's exchanges moved
per executed query (growth of ``trino_tpu_mesh_exchange_bytes_total``, every
kind)."""

from ._exchange import bytes_per_query


def read(run):
    moved = bytes_per_query(run)
    return None if moved is None else moved / 1e6
