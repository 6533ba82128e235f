"""The check that decides ``correct``, driven end to end on the CPU.

A tiny cell (the system's ``linear`` model, STC with error feedback) runs
the whole of a benchmark run except the look for a chip: sound, it comes
out correct; with the control in the program's place, or with a fault
planted in the program, it comes out not correct.  Its last compared round
brings clients back to their stored residuals."""
import time
from pathlib import Path

import pytest

import control
import harness
import spec

TINY = Path(__file__).resolve().parent / "fixtures" / "tiny"
SEED = 2**33 + 17


@pytest.fixture(scope="module")
def tiny():
    return spec.Bench(root=TINY, bench_dir=TINY)


def _run(tiny, seed=SEED):
    return harness.run_cell(tiny, "tiny_stc", seed, 0.5, False, time.perf_counter(),
                            require_tpu=False)[0]


def test_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(c["value"] < 1e-6 for c in out["checks"].values())


def test_control_is_not_correct(tiny):
    numbers = control.control_check(tiny, "tiny_stc", SEED)["numbers"]
    limits = tiny.limits("tiny_stc")
    assert any(v > limits[k] for k, v in numbers.items()), numbers


@pytest.mark.parametrize("fault,fails", [
    ("unchanged_state", "change_gap"),
    ("half_cohort", "grad_support"),
    ("scaled_weights", "grad_median"),
    ("no_residual", "last_diff"),
])
def test_planted_fault_is_not_correct(tiny, fault, fails):
    with control.FAULTS[fault]():
        out = _run(tiny)
    assert not out["correct"]
    assert out["checks"][fails]["value"] > out["checks"][fails]["limit"]

