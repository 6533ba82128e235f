"""The check that decides ``correct``, driven end to end on the CPU.

Two tiny cells run the whole of a benchmark run except the look for a
chip: ``tiny_stc`` (the system's ``linear`` model, STC with error
feedback) and ``tiny_lm_lora`` (the system's ``tiny_lm`` decoder under
LoRA over a frozen base made from the seed, token traffic, clients of
uneven size, STC with error feedback).  Sound, each comes out correct;
with the control in the program's place, or with a fault planted in the
program, it comes out not correct.  Their last compared round brings
clients back to their stored residuals."""
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


def _run(tiny, cell, seed=SEED):
    return harness.run_cell(tiny, cell, seed, 0.5, False, time.perf_counter(),
                            require_tpu=False)[0]


@pytest.mark.parametrize("cell,exact", [("tiny_stc", True), ("tiny_lm_lora", False)])
def test_sound_run_is_correct(tiny, cell, exact):
    """``exact``: the linear cell's program and reference agree to
    rounding; the decoder's STC keeps a few different entries where an
    update lies at a tile's threshold, and later rounds drift by them."""
    out = _run(tiny, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "setup_s"}
    if exact:
        assert all(c["value"] < 1e-6 for c in out["checks"].values())


@pytest.mark.parametrize("cell", ["tiny_stc", "tiny_lm_lora"])
def test_control_is_not_correct(tiny, cell):
    numbers = control.control_check(tiny, cell, SEED)["numbers"]
    limits = tiny.limits(cell)
    assert any(v > limits[k] for k, v in numbers.items() if k in limits), numbers


@pytest.mark.parametrize("cell,fault,fails", [
    ("tiny_stc", "unchanged_state", "change_gap"),
    ("tiny_stc", "half_cohort", "grad_support"),
    ("tiny_stc", "scaled_weights", "grad_median"),
    ("tiny_stc", "no_residual", "last_diff"),
    ("tiny_lm_lora", "unchanged_state", "grad_gap"),
    ("tiny_lm_lora", "half_cohort", "grad_support"),
    ("tiny_lm_lora", "no_residual", "last_gap"),
    ("tiny_lm_lora", "own_base", "grad_gap"),
    ("tiny_lm_lora", "count_blind_weights", "grad_median"),
])
def test_planted_fault_is_not_correct(tiny, cell, fault, fails):
    with control.FAULTS[fault]():
        out = _run(tiny, cell)
    assert not out["correct"]
    assert out["checks"][fails]["value"] > out["checks"][fails]["limit"]

