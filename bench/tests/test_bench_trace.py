"""Tests of the trace reduction (``bench/trace_reduce.py``).

The synthetic cases pin the arithmetic, laid out as the TPU trace is:
host spans ``bench.*``, device ops named by their HLO instruction, kernels
by the jitted wrapper of their ``pallas_call``, and one module event per
program execution.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce as tr  # noqa: E402


def _synthetic():
    host = [(0, 100, "bench.window"), (10, 30, "bench.stage"),
            (30, 32, "bench.dispatch"), (32, 60, "bench.block")]
    ops = [(35, 50, "fusion.1", False, "fusion.1"),
           (50, 58, "custom-call.1", True, "rss_kernel"),
           (45, 52, "copy.2", False, "copy.2"),          # overlaps both
           (120, 130, "fusion.9", False, "fusion.9")]     # after the window
    mods = [(34, 59, "jit_online")]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops,
                                                        "modules": mods}}}


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [[0, 4], [5, 10]]


def test_busy_kernel_and_module_time():
    red = tr.reduce_trace(_synthetic())
    assert red["window_ns"] == 100
    assert red["busy_ns"] == 23                     # [35, 58]
    assert red["kernel_ns"] == 8 and red["kernel_count"] == 1
    assert red["by_kernel"] == {"rss_kernel": 8}
    assert red["module_ns"] == {"jit_online": 23}
    assert red["module_kernel_ns"] == {"jit_online": 8}
    assert red["module_count"] == {"jit_online": 1}


def test_gaps_are_labelled_by_the_open_host_span():
    red = tr.reduce_trace(_synthetic())
    # [58, 100]: no bench span open but the window; [0, 35]: in the stage
    assert red["gaps"] == [("host: outside bench spans", 42),
                           ("bench.stage", 35)]
    assert red["top_ops"][0] == ("fusion.1", 15)


def test_no_window_span_is_an_error():
    t = _synthetic()
    t["host"] = [h for h in t["host"] if h[2] != "bench.window"]
    with pytest.raises(ValueError):
        tr.reduce_trace(t)
