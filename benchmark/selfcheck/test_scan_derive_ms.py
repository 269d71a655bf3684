"""``scan_derive_ms`` on planted phase samples: the ``scan_derive``
spans' milliseconds per executed query; ``None`` on a program without
the span (one that refills every fresh constraint)."""

import importlib

import pytest

import run as bench_run

P = "trino_tpu_query_phase_seconds"


def reader():
    return importlib.import_module("layer_metrics.scan_derive_ms")


def phase(name, count, seconds):
    return {f'{P}_count{{phase="{name}"}}': float(count),
            f'{P}_sum{{phase="{name}"}}': float(seconds)}


def test_the_derive_spans_per_executed_query():
    run = bench_run.Run()
    run.engine_before = {**phase("execute", 4, 1.0),
                         **phase("scan_derive", 2, 0.030)}
    run.engine_after = {**phase("execute", 14, 3.0),
                        **phase("scan_derive", 5, 0.075)}
    assert reader().read(run) == pytest.approx(1e3 * 0.045 / 10)


def test_zero_where_the_window_derived_nothing():
    run = bench_run.Run()
    run.engine_before = {**phase("execute", 4, 1.0),
                         **phase("scan_derive", 2, 0.030)}
    run.engine_after = {**phase("execute", 14, 3.0),
                        **phase("scan_derive", 2, 0.030)}
    assert reader().read(run) == 0.0


def test_none_on_a_program_without_the_span():
    run = bench_run.Run()
    run.engine_before = {**phase("execute", 4, 1.0),
                         **phase("scan_fill", 9, 3.0)}
    run.engine_after = {**phase("execute", 14, 3.0),
                        **phase("scan_fill", 40, 30.0)}
    assert reader().read(run) is None
