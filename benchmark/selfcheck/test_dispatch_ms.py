"""``dispatch_ms`` on planted ``engine_before`` / ``engine_after``
dicts: the ``dispatch`` spans' seconds per executed query; 0.0 on a
program without the span (one that waits inside ``device_execute``),
and ``None`` where the program exports no phase family at all."""

import importlib

import pytest

import run as bench_run

P = "trino_tpu_query_phase_seconds"


def reader():
    return importlib.import_module("layer_metrics.dispatch_ms")


def phase(name, count, seconds):
    return {f'{P}_count{{phase="{name}"}}': float(count),
            f'{P}_sum{{phase="{name}"}}': float(seconds)}


def window(*spans):
    """A window of 10 executed queries over a set-up of 4; ``spans``:
    (name, seconds per query) closed in every query."""
    run = bench_run.Run()
    before, after = {}, {}
    for name, per_query_s in (("execute", 0.010),) + spans:
        before.update(phase(name, 4, 4 * per_query_s * 3))
        after.update(phase(name, 14, 4 * per_query_s * 3
                           + 10 * per_query_s))
    run.engine_before, run.engine_after = before, after
    return run


def test_the_dispatch_spans_per_executed_query():
    run = window(("dispatch", 0.0015), ("host_read", 0.004))
    assert reader().read(run) == pytest.approx(1.5)


def test_the_parent_reads_zero():
    """The parent waits inside ``device_execute`` and closes no
    ``dispatch`` span: 0.0, not nothing, so its traced run ends with
    exit code 0."""
    run = window(("device_execute", 0.0050), ("host_read", 0.004))
    assert reader().read(run) == 0.0


def test_none_where_the_program_has_no_phase_family():
    run = bench_run.Run()
    run.engine_before = {"trino_tpu_splits_read_total": 3.0}
    run.engine_after = {"trino_tpu_splits_read_total": 5.0}
    assert reader().read(run) is None
