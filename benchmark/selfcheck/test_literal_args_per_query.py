"""``literal_args_per_query`` on planted counter samples: the literals
bound to programs as arguments per executed query, over every program
kind; 0.0 where the family grew by nothing, ``None`` on a program
without it (one that bakes every literal)."""

import importlib

import pytest

import run as bench_run

P = "trino_tpu_query_phase_seconds"
A = "trino_tpu_program_literal_args_total"


def reader():
    return importlib.import_module("layer_metrics.literal_args_per_query")


def window(args_before, args_after, executed=(4, 14)):
    run = bench_run.Run()
    run.engine_before = {f'{P}_count{{phase="execute"}}': executed[0],
                         **{f'{A}{{kind="{k}"}}': v
                            for k, v in args_before.items()}}
    run.engine_after = {f'{P}_count{{phase="execute"}}': executed[1],
                        **{f'{A}{{kind="{k}"}}': v
                           for k, v in args_after.items()}}
    return run


def test_the_arguments_of_every_kind_per_executed_query():
    run = window({"stream_full": 12.0, "chain": 4.0},
                 {"stream_full": 42.0, "chain": 14.0, "stream": 5.0})
    assert reader().read(run) == pytest.approx((30 + 10 + 5) / 10)


def test_zero_where_the_window_bound_none():
    run = window({"chain": 4.0}, {"chain": 4.0})
    assert reader().read(run) == 0.0


def test_none_without_the_family_or_queries():
    assert reader().read(window({}, {})) is None
    assert reader().read(window({"chain": 1.0}, {"chain": 2.0},
                                executed=(3, 3))) is None
