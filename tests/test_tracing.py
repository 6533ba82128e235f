"""Profiler spans of the federated round, read back from a real trace.

Every span the round puts on the host's profiler clock (``fl.round``,
``fl.cohort``, ``fl.inputs``, ``fl.dispatch``, ``fl.fetch``,
``fl.finalize``, the stores' ``fl.<store>.insert``, ``fl.checkpoint``) and
the ``jax.named_scope`` stages of the fused round program.  Each test
runs a tiny fused federation (STC, every client selected every round, so
only round 0 inserts pool and error-feedback rows) under
``jax.profiler.trace`` and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.

Every profiler session of the suite lives in this file: a process holds
one profiler session at a time, and the suite's workers split by file.
"""
import glob
import os

import jax
import numpy as np
import pytest

import repro as easyfl
from repro.core import batched

PHASES = ("fl.cohort", "fl.inputs", "fl.dispatch", "fl.fetch", "fl.finalize")
STAGES = ("fl.train", "fl.compress", "fl.aggregate", "fl.apply")
CLIENTS, ROUNDS = 6, 3


def _config(**extra):
    cfg = {
        "model": "linear", "dataset": "synthetic",
        "data": {"num_clients": CLIENTS, "batch_size": 32},
        "server": {"rounds": ROUNDS, "clients_per_round": CLIENTS},
        "client": {"local_epochs": 2, "lr": 0.1, "compression": "stc"},
        "resources": {"execution": "batched"},
    }
    for k, v in extra.items():
        cfg.setdefault(k, {}).update(v)
    return cfg


def _spans(trace_dir):
    """The ``fl.*`` host spans of the trace as ``(name, start, end, stats)``,
    in start order."""
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fl."):
                    start = int(ev.start_ns)
                    out.append((ev.name, start, start + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _run_traced(trace_dir, cfg):
    easyfl.reset()
    easyfl.init(cfg)
    try:
        with jax.profiler.trace(str(trace_dir)):
            res = easyfl.run()
    finally:
        easyfl.reset()
    return res


@pytest.fixture(scope="module")
def sync_run(tmp_path_factory):
    """Three synchronous fused rounds under the profiler, the shapes of the
    round program's arguments as its first call saw them, and each call's
    longest client (``max(n_steps)``)."""
    programs, longest = [], []
    make = batched.make_round_program

    def recording(*args, **kwargs):
        program = make(*args, **kwargs)

        def call(*a):
            if not programs:
                programs.append((program, jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)))
            longest.append(int(np.max(np.asarray(a[4]))))
            return program(*a)
        return call

    trace_dir = tmp_path_factory.mktemp("sync-trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batched, "make_round_program", recording)
        res = _run_traced(trace_dir, _config())
    assert len(res["history"]) == ROUNDS
    return _spans(trace_dir), programs[0], longest


def test_each_phase_once_per_round_in_order_inside_its_round(sync_run):
    spans = sync_run[0]
    rounds = _named(spans, "fl.round")
    assert [s[3]["round"] for s in rounds] == list(range(ROUNDS))
    assert [s[3]["step_num"] for s in rounds] == list(range(ROUNDS))
    for name in PHASES:
        assert [s[3]["round"] for s in _named(spans, name)] == \
            list(range(ROUNDS)), name
    for r, outer in enumerate(rounds):
        phases = [_named(spans, name)[r] for name in PHASES]
        assert all(_inside(p, outer) for p in phases)
        # one after another, no overlap
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1], (a[0], b[0], r)


def test_inputs_span_carries_the_cohort_shape(sync_run):
    spans, _, longest = sync_run
    inputs = _named(spans, "fl.inputs")
    for s in inputs:
        # 6 clients bucket to 8; 2 epochs of cyclic batches to a power of 2
        assert s[3]["clients"] == CLIENTS and s[3]["bucket"] == 8
        assert s[3]["steps"] >= 2 and s[3]["steps"] & (s[3]["steps"] - 1) == 0
        # the loop runs to the longest client, within the bucket
        assert 2 <= s[3]["loop_steps"] <= s[3]["steps"]
    assert [s[3]["loop_steps"] for s in inputs] == longest


@pytest.mark.parametrize("store", ["fl.data-pool.insert", "fl.ef-store.insert"])
def test_store_inserts_only_in_round_zero_inside_inputs(sync_run, store):
    spans = sync_run[0]
    inserts = _named(spans, store)
    assert len(inserts) == 1 and inserts[0][3]["rows"] == CLIENTS
    assert _inside(inserts[0], _named(spans, "fl.inputs")[0])


def test_round_program_carries_the_four_stage_scopes(sync_run):
    program, shapes = sync_run[1]
    text = program.lower(*shapes).as_text(debug_info=True)
    for stage in STAGES:
        assert f"/{stage}/" in text, stage


def test_deferred_rounds_overlap_next_prep_and_checkpoint_span(tmp_path):
    """Under ``tracking.round_sync=False`` round R's finalize runs after
    round R+1's prep has been dispatched; its deferred fetch runs inside
    that finalize.  A checkpoint round finalizes at once and saves inside
    its own ``fl.round``."""
    cfg = _config(server={"test_every": 0}, tracking={"round_sync": False},
                  checkpoint={"every": ROUNDS, "dir": str(tmp_path / "ck")})
    _run_traced(tmp_path / "trace", cfg)
    spans = _spans(tmp_path / "trace")
    rounds = _named(spans, "fl.round")
    inputs, finals = _named(spans, "fl.inputs"), _named(spans, "fl.finalize")
    fetches = _named(spans, "fl.fetch")
    assert [s[3]["round"] for s in finals] == list(range(ROUNDS))
    # round 0 finalizes inside round 1, after round 1's inputs
    assert _inside(finals[0], rounds[1]) and inputs[1][2] <= finals[0][1]
    assert _inside(fetches[0], finals[0])
    ck, = _named(spans, "fl.checkpoint")
    assert ck[3]["round"] == ROUNDS - 1 and _inside(ck, rounds[-1])
    assert finals[-1][2] <= ck[1]
