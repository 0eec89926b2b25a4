"""What PR 39 adds to the benchmark, as new files beside the old: the reader
of ``layer_metrics/serve_kv_read.py`` on a made-up trace (the program's
``serve/kv_read`` record present: the share; absent: ``None``), and its two
entries of ``BENCHMARK.json``, one under each end-to-end metric its cells
report."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.layer_metrics import serve_kv_read                # noqa: E402


def made_up(records, job="serve"):
    from chipbench.layer_metrics import _program_spans as P
    from chipbench.trace import Device, Trace
    ticks = [(0.0, 0.1, "serve/tick", 1, 6), (0.1, 0.2, "serve/tick", 2, 6)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    ctx = types.SimpleNamespace(
        cell={"job": job, "serving": {"num_slots": 28, "max_model_len": 1024}},
        log=lambda msg: None, state={"program_spans": (placed, {})})
    trace = Trace([Device("/device:TPU:0", [], [])], [(0.0, 0.2, "window")])
    return ctx, {}, trace


RECORDS = [(0.05, 0.05, "serve/kv_read", 5120, 28672),
           (0.15, 0.15, "serve/kv_read", 6144, 28672),
           (0.15, 0.15, "serve/kv_live", 9, 9),         # another record
           (0.30, 0.30, "serve/kv_read", 28672, 28672)]  # after the window


@pytest.mark.parametrize("name", sorted(serve_kv_read.METRICS))
def test_kv_read_share_is_the_mean_share_of_the_windows_ticks(name):
    """Two decode ticks that fetched 5,120 and 6,144 of 28 x 1,024 columns
    a layer; the record after the window is not counted."""
    ctx, record, trace = made_up(RECORDS)
    assert serve_kv_read.METRICS[name](ctx, record, trace) == pytest.approx(
        100 * (5120 + 6144) / 2 / 28672)


@pytest.mark.parametrize("case", ("no trace", "no record", "no tick",
                                  "another job"))
def test_kv_read_share_is_left_out_where_there_is_nothing_to_read(case):
    """Without a trace, for a program that records no ``serve/kv_read`` (the
    parent of this PR), before any record falls inside the window and
    outside a serving cell: ``None``, and nothing raises."""
    read = serve_kv_read.kv_read_share
    if case == "no trace":
        ctx, record, _ = made_up(RECORDS)
        assert read(ctx, record, None) is None
    elif case == "no record":
        assert read(*made_up([r for r in RECORDS
                              if r[2] != "serve/kv_read"])) is None
    elif case == "no tick":
        assert read(*made_up(RECORDS[-1:])) is None
    else:
        assert read(*made_up(RECORDS, job="train")) is None


def test_manifest_lists_the_share_under_each_metric_its_cells_report():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by = {m["name"]: m for m in man["per_layer"]}
    assert set(serve_kv_read.METRICS) <= set(by)
    # by membership, wherever they stand: entries are appended behind them
    # (PR 57 put the twenty-one that waited on this pin there)
    names = [m["name"] for m in man["per_layer"]]
    assert names.count("kv_read_share") == 1 == \
        names.count("kv_read_share.backlog")
    for name, moves in (("kv_read_share", "itl_p95_ms"),
                        ("kv_read_share.backlog", "serve_tokens_per_s")):
        m = by[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", "lower", "program_counter", "Kernels",
                                moves)
    assert by["kv_read_share.backlog"]["workloads"] == \
        ["opt-1.3b.serve-backlog"]
    assert "opt-1.3b.serve-chat" in by["kv_read_share"]["workloads"]
