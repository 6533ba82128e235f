"""The reduction from a profiler trace to device metrics, on a slice of a
real TPU v5e trace (the end of one ``resnet18_stc_c16`` round)."""
import json
from pathlib import Path

import numpy as np
import pytest

import devtrace
import spec

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_v5e_round_end.json"
STC = r"^%_stc_batched_padded[.0-9]* = .*custom-call"
AGG = r"^%_aggregate_padded[.0-9]* = .*custom-call"


@pytest.fixture(scope="module")
def trace():
    doc = json.loads(FIXTURE.read_text())
    return [tuple(e) for e in doc["events"]], doc["lo"], doc["hi"]


def _busy_by_ticks(events, lo, hi, tick=1000):
    """Busy time counted on a 1 us grid: an independent union."""
    grid = np.zeros((hi - lo) // tick + 1, bool)
    for e in devtrace.device_ops(events, 0):
        s, t = max(e[3], lo), min(e[3] + e[4], hi)
        if t > s:
            grid[(s - lo) // tick:(t - lo + tick - 1) // tick] = True
    return grid.sum() * tick


def test_devices_and_busy_time(trace):
    events, lo, hi = trace
    assert devtrace.devices(events) == [0]
    busy = devtrace.busy_ns(events, 0, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy == pytest.approx(_busy_by_ticks(events, lo, hi), rel=0.02)


def test_kernel_time_by_name(trace):
    events, lo, hi = trace
    agg = [e for e in devtrace.device_ops(events, 0) if e[2].startswith("%_aggregate_padded")]
    assert len(agg) == 1
    assert devtrace.op_time_ns(events, AGG, lo, hi) == agg[0][4]
    stc = devtrace.op_time_ns(events, STC, lo, hi)
    assert 0 < stc < hi - lo
    # clipping: half the window holds at most the whole
    assert devtrace.op_time_ns(events, STC, lo, (lo + hi) // 2) <= stc


def test_top_ops_leave_out_loops_and_cut_names(trace):
    events, lo, hi = trace
    top = devtrace.top_ops(events, 0, lo, hi)
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True) and sum(secs) <= (hi - lo) / 1e9 * 2
    assert all(len(name) <= 120 and not name.startswith("%while") for name, _ in top)


def test_idle_gaps_add_up_to_idle_time(trace):
    events, lo, hi = trace
    gaps = devtrace.idle_gaps(events, 0, lo, hi, n=1000)
    idle = (hi - lo) - devtrace.busy_ns(events, 0, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx(idle / 1e9, rel=1e-9)


def test_window_span_is_found():
    ev = [("/host:CPU", "python3", "bench.window", 100, 50),
          ("/host:CPU", "python3", "other", 90, 500)]
    assert devtrace.span(ev, "bench.window") == (100, 150)
    with pytest.raises(ValueError):
        devtrace.span(ev, "missing")


def test_union_merges_overlaps_and_nesting():
    assert devtrace.union([(0, 10), (2, 3), (10, 12), (20, 25)]) == [(0, 12), (20, 25)]


def test_roofline_readers_on_the_trace(trace):
    """FedAvg's share from the one kernel call in the slice: the least
    bytes (16 real clients of ResNet-18's update) at 819 GB/s over its
    device time; STC's reader stays silent in a cell without STC."""
    events, lo, hi = trace
    bench = spec.Bench()
    cfg = bench.config("resnet18_cifar10")
    ctx = {"events": events, "lo": lo, "hi": hi, "trace_chips": [0], "rounds": 1,
           "bench": bench, "device_kind": "TPU v5 lite", "update_sizes": [cfg["params"]],
           "traffic": bench.traffic("cifar10_iid_c16_stc")}
    share = bench.metric_reader("fedavg_agg_roofline").read(ctx)
    agg_ns = devtrace.op_time_ns(events, AGG, lo, hi)
    least = (4 * 16 * cfg["params"] + 4 * 16 + 4 * cfg["params"]) / 819e9
    assert share == pytest.approx(100 * least / (agg_ns / 1e9))
    assert 0 < share <= 100
    ctx["traffic"] = bench.traffic("leaf_writers_c100")
    assert bench.metric_reader("stc_topk_roofline").read(ctx) is None


def test_idle_share_reader():
    bench = spec.Bench()
    r = bench.metric_reader("device_idle_share")
    assert r.read({"busy_s": 3.0, "trace_window_s": 4.0}) == pytest.approx(25.0)
