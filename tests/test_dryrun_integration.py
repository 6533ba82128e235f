"""Dry-run integration: the real launcher in a subprocess (it owns the
512-device XLA flag), reduced sequence for CPU-compile speed."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(tmp_path, *args):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--out", str(tmp_path), *args]
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_dryrun_single_pod_train(tmp_path):
    out = _run_dryrun(tmp_path, "--arch", "rwkv6-1.6b", "--shape",
                      "train_4k", "--seq-override", "256")
    assert "[OK]" in out
    files = os.listdir(tmp_path)
    assert len(files) == 1
    rec = json.load(open(tmp_path / files[0]))
    assert rec["mesh_shape"] == [16, 16]
    rl = rec["roofline"]
    assert rl["flops"] > 0 and rl["hbm_bytes"] > 0
    assert rl["dominant"] in ("compute", "memory", "collective")
    assert rec["collectives"]["total_bytes"] > 0     # FSDP gathers exist
    assert rec["hlo_analysis"]["while_trips"]        # scan over layers seen


@pytest.mark.slow
def test_dryrun_multi_pod_fed_round(tmp_path):
    out = _run_dryrun(tmp_path, "--arch", "glm4-9b", "--shape", "train_4k",
                      "--multi-pod", "--step", "fed", "--seq-override", "256")
    assert "[OK]" in out
    rec = json.load(open(tmp_path / os.listdir(tmp_path)[0]))
    assert rec["mesh_shape"] == [2, 16, 16]
    assert rec["step"] == "fed"
    assert rec["roofline"]["collective_bytes"] > 0   # the pod-sync collective


def test_dryrun_failure_exits_nonzero(tmp_path):
    """A dry run that raises prints [FAIL] and the launcher exits non-zero
    (a caught failure must not look like success to a caller)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # a negative sequence length cannot be lowered for any arch
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--out", str(tmp_path),
         "--arch", "rwkv6-1.6b", "--shape", "train_4k",
         "--seq-override", "-8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert "[FAIL] rwkv6-1.6b train_4k" in r.stdout
    assert r.returncode != 0
    assert "1 dry run(s) failed" in r.stderr
    assert os.listdir(tmp_path) == []
