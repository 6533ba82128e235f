"""BENCHMARK.json against the benchmark's contract, and discovery by name."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import spec
from peaks import peaks

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).resolve().parent / "fixtures" / "tiny"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
COMPARED = {"loss_gap"} | {f"{u}_{k}" for u in ("grad", "change", "last")
                           for k in ("gap", "median", "diff", "support")}


@pytest.fixture(scope="module")
def bench():
    return spec.Bench()


def test_top_level_keys_and_limits(bench):
    doc = bench.doc
    assert set(doc) == KEYS
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    assert doc["command"][1:] == ["bench/run.py"] and doc["paths"] == ["bench"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("key,fields", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_the_contract_keys(bench, key, fields):
    names = [e["name"] for e in bench.doc[key]]
    assert len(names) == len(set(names))
    for e in bench.doc[key]:
        assert set(e) - {"workloads"} == fields, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


def test_bounds_and_metric_sources(bench):
    names = {m["name"] for m in bench.doc["end_to_end"]}
    assert "setup_s" in names
    for m in bench.doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bench.doc["per_layer"]:
        assert m["moves"] in names
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_every_cell_reports_what_the_contract_asks(bench):
    for wl in bench.doc["workloads"]:
        e2e = {m["name"] for m in bench.end_to_end(wl["name"])}
        layer = bench.per_layer(wl["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)
        assert wl["chips"] in (1, 4) and len(wl["why"]) <= 200


def test_every_named_file_is_found(bench):
    for c in bench.doc["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert hasattr(bench.reference(c["name"]), "forward")
        assert hasattr(bench.count(c["name"]), "forward_flops")
    for wl in bench.doc["workloads"]:
        assert bench.traffic(wl["traffic"])["clients_per_round"] > 0
        limits = bench.limits(wl["name"])
        assert limits["rounds"] >= 2 and set(limits) - {"rounds"} <= COMPARED
    for m in bench.doc["per_layer"]:
        assert callable(bench.metric_reader(m["name"]).read)


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(TINY, root)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    (root / "metrics").mkdir()
    (root / "metrics" / "rounds_seen.py").write_text("def read(ctx):\n    return ctx['rounds']\n")
    shutil.copy(root / "traffic" / "tiny_stc.json", root / "traffic" / "tiny_more.json")
    shutil.copy(root / "limits" / "tiny_stc.json", root / "limits" / "tiny_more.json")
    doc["workloads"].append({**doc["workloads"][0], "name": "tiny_more", "traffic": "tiny_more"})
    doc["per_layer"].append({"name": "rounds_seen", "unit": "rounds", "better": "higher",
                             "source": "host_clock", "layer": "harness",
                             "moves": "samples_per_s", "workloads": ["tiny_more"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = spec.Bench(root=root, bench_dir=root)
    assert b.traffic(b.workload("tiny_more")["traffic"])["population"] == 12
    assert [m["name"] for m in b.per_layer("tiny_more")] == ["rounds_seen"]
    assert b.per_layer("tiny_stc") == []
    assert b.metric_reader("rounds_seen").read({"rounds": 7}) == 7


def test_a_class_of_cells_reads_its_quantitys_reader(bench):
    assert spec.quantity("samples_per_s.host") == "samples_per_s"
    assert bench.metric_reader("device_idle_share.host") is bench.metric_reader("device_idle_share")
    assert [m["name"] for m in bench.end_to_end("femnist_cnn_c100")] == \
        ["samples_per_s.host", "round_s_p90.host", "setup_s"]


def test_unknown_names_are_errors(bench):
    with pytest.raises(spec.SpecError):
        bench.workload("no_such_cell")
    with pytest.raises(spec.SpecError):
        bench.traffic("no_such_traffic")


def test_peak_table_is_keyed_by_device_kind():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        peaks("cpu")


@pytest.mark.parametrize("args", [
    ["--workload", "resnet18_stc_c16", "--seed", "3", "--seconds", "1", "--trace", "0"],
    ["--workload", "no_such_cell", "--seed", "3", "--seconds", "1", "--trace", "0"],
])
def test_command_refuses_without_a_tpu(args):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(ROOT)}
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"), *args], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_number_compared_is_named():
    import numpy as np

    import reference

    tree = [{"w": np.full((3, 4), float(r)), "b": np.full((4,), 0.5 * r)} for r in range(4)]
    numbers = reference.compare({"loss": [1.0, 0.9, 0.8], "params": [None, *tree[1:]]},
                                {"loss": [1.0, 0.9, 0.8], "params": tree})
    assert set(numbers) == COMPARED and all(v == 0.0 for v in numbers.values())
