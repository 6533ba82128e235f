"""The readers of the program's host spans (``host_prep_ms``,
``prep_idle_share``, ``pool_insert_ms``) on hand-built traces whose
answers are known, and on a slice of a real TPU v5e trace."""
import json
from pathlib import Path

import pytest

import devtrace
import spec

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_v5e_round_end.json"
MS = 1_000_000
LO, HI = 1000 * MS, 2000 * MS
HOST = ("/host:CPU", "python3")


def _host(name, start_ms, end_ms):
    return (*HOST, name, LO + start_ms * MS, (end_ms - start_ms) * MS)


def _op(device, start_ms, end_ms):
    return (f"/device:TPU:{device}", "XLA Ops", "%fusion.1 = f32[8]",
            LO + start_ms * MS, (end_ms - start_ms) * MS)


# two rounds of a 1 s window: prep [10, 150) and [500, 650) ms, the pool
# inserts nested in it; chip 0 busy [100, 300) and [600, 900), chip 1 all
# the time; spans outside the window do not count
ROUNDS = [
    _host("fl.round", 0, 500), _host("fl.cohort", 10, 60), _host("fl.inputs", 60, 150),
    _host("fl.data-pool.insert", 70, 120), _host("fl.dispatch", 150, 160),
    _host("fl.fetch", 160, 300), _host("fl.finalize", 300, 320),
    _host("fl.round", 500, 1000), _host("fl.cohort", 500, 550), _host("fl.inputs", 550, 650),
    _host("fl.data-pool.insert", 560, 640),
    _host("fl.cohort", -40, -10), _host("fl.data-pool.insert", 1000, 1100),
]
DEVICE = [_op(0, 100, 300), _op(0, 600, 900), _op(1, 0, 1000)]


@pytest.fixture(scope="module")
def bench():
    return spec.Bench()


def _ctx(events, chips=(0,), rounds=2, lo=LO, hi=HI):
    return {"events": list(events), "lo": lo, "hi": hi, "trace_chips": list(chips),
            "rounds": rounds}


def _read(bench, name, ctx):
    return bench.metric_reader(name).read(ctx)


def test_exact_values(bench):
    ctx = _ctx(ROUNDS + DEVICE)
    # (50 + 90 + 50 + 100) ms of prep over 2 rounds
    assert _read(bench, "host_prep_ms", ctx) == pytest.approx(145.0)
    assert _read(bench, "pool_insert_ms.host", ctx) == pytest.approx((50 + 80) / 2)
    # prep 290 ms, of which chip 0 is busy 50 + 50 ms: 190 ms of 1000 idle
    assert _read(bench, "prep_idle_share", ctx) == pytest.approx(19.0)
    # chip 1 never idles: the mean over chips halves it
    assert _read(bench, "prep_idle_share.host", _ctx(ROUNDS + DEVICE, chips=(0, 1))) == \
        pytest.approx(9.5)


@pytest.mark.parametrize("name", ["host_prep_ms", "host_prep_ms.host", "prep_idle_share",
                                  "prep_idle_share.host", "pool_insert_ms.host"])
def test_nothing_without_the_spans(bench, name):
    """A program without spans (the trace holds the benchmark's window and
    the Python tracer's frames only) reads nothing, and does not raise."""
    others = [(*HOST, "bench.window", LO, HI - LO), (*HOST, "$rounds.py:576 run_round", LO, MS)]
    assert _read(bench, name, _ctx(DEVICE + others)) is None
    # spans that all lie outside the window count as absent too
    outside = [_host("fl.cohort", -40, -10), _host("fl.inputs", -10, -5),
               _host("fl.data-pool.insert", 1000, 1100)]
    assert _read(bench, name, _ctx(DEVICE + outside)) is None


def test_overlap_of_interval_lists():
    import spans

    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap_ns([], [(0, 5)]) == 0


@pytest.mark.parametrize("cover", [(0.0, 1.0), (0.1, 0.4), (0.45, 0.9)])
def test_prep_idle_share_within_device_idle_share_on_the_v5e_trace(bench, cover):
    """Synthetic prep spans over a share of a real round's end: the chip's
    idle time inside them is at most all of its idle time, and equal to it
    where prep covers the whole window."""
    doc = json.loads(FIXTURE.read_text())
    events, lo, hi = [tuple(e) for e in doc["events"]], doc["lo"], doc["hi"]
    a, b = (lo + int(f * (hi - lo)) for f in cover)
    mid = (a + b) // 2
    events += [(*HOST, "fl.cohort", a, mid - a), (*HOST, "fl.inputs", mid, b - mid)]
    ctx = {"events": events, "lo": lo, "hi": hi, "trace_chips": [0], "rounds": 1}
    ctx["busy_s"] = devtrace.busy_ns(events, 0, lo, hi) / 1e9
    ctx["trace_window_s"] = (hi - lo) / 1e9
    idle = _read(bench, "device_idle_share", ctx)
    prep = _read(bench, "prep_idle_share", ctx)
    assert 0.0 <= prep <= idle
    if cover == (0.0, 1.0):
        assert prep == pytest.approx(idle, abs=1e-9)
    assert _read(bench, "host_prep_ms", ctx) == pytest.approx((b - a) / 1e6)
