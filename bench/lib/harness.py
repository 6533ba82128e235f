"""One run of one cell: set-up, the measured window, the check, one line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the system from the cell's files, makes its weights (and any
frozen base) and data from the seed, and drives it through its first
rounds: they compile every program the window uses, fill what the traffic
fills, and are the rounds the reference follows.  Then
``Trainer.run_round`` is called back to back (closed loop) until
``--seconds`` have passed; the round in flight then finishes, and the
window ends with it.  Afterwards the device's peak memory is read, the
system is freed, and the plain reference, which makes its own frozen base
from the seed again, replays the first rounds to decide ``correct``: as
many as bring clients back to their stored residuals and data rows, and
the data pool to its first evictions.  The last line of standard output
is the result; the numbers compared, each with its limit, are the last
lines of standard error and the last key of the result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from spec import Bench, SpecError, quantity

WINDOW_SPAN = "bench.window"


def log(t_start: float, msg: str) -> None:
    print(f"[{time.perf_counter() - t_start:8.3f}s] {msg}", file=sys.stderr, flush=True)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def enable_compile_cache(root) -> str:
    """JAX's persistent cache at a fixed path inside the checkout; any
    directory the environment names is overridden, so two checkouts never
    share one."""
    import jax

    path = os.path.join(str(root), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int) -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is {backend!r}; "
                         f"this benchmark measures the chip only")
    if jax.device_count() < n:
        raise SystemExit(f"the cell needs {n} chips, JAX finds {jax.device_count()}")


def device_info() -> Dict[str, Any]:
    import jax

    devs = jax.local_devices()
    peak = max(float((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def p90(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 90))


def judge(numbers: Dict[str, float], limits: Dict[str, Any]):
    """Each number a cell is held to, beside its limit, and whether all
    of them are within it."""
    checks = {k: {"value": numbers[k], "limit": float(v)}
              for k, v in limits.items() if k != "rounds"}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(bench: Bench, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             reference_of: Optional[Callable[[], Dict[str, Any]]] = None,
             keep_programs: bool = False):
    """Everything after argument parsing: the result line, and every number
    the check can compare (``reference.compare``) with the per-leaf norms
    they come from (``reference.leaf_table``).  ``require_tpu=False`` lets
    a test drive a run on the CPU; ``reference_of`` hands back the plain
    reference of this seed where a caller has it already, and
    ``keep_programs`` keeps the compiled programs for a next run in the
    same process."""
    import jax

    wl = bench.workload(workload)
    config = bench.config(wl["config"])
    ref = bench.reference(wl["config"])
    traffic = bench.traffic(wl["traffic"])
    limits = bench.limits(workload)
    per_layer = bench.per_layer(workload)
    readers = {m["name"]: bench.metric_reader(m["name"]) for m in per_layer}
    end_to_end = bench.end_to_end(workload)
    if require_tpu:
        require_chips(int(wl["chips"]))
        enable_compile_cache(bench.root)

    import compile_events
    import reference as refm
    import system
    import traffic as tr

    compiles = compile_events.CompileEvents()
    fed = tr.Federation(traffic, config, seed)
    weights = refm.init_weights(ref, config, seed)
    frozen = refm.init_frozen(ref, config, seed)
    trainer = system.build(config, traffic, fed, weights, seed, frozen)
    del weights, frozen

    # --- set-up: the first rounds, kept for the check; then until no round compiles
    check_rounds = int(limits["rounds"])
    warm = max(int(traffic["warm_rounds"]), check_rounds)
    prog: Dict[str, Any] = {"loss": [], "params": [None]}
    r = 0
    while True:
        c0, a = compiles.count, time.perf_counter()
        m = trainer.run_round(r)
        system.block(trainer)
        log(t_start, f"set-up round {r}: {time.perf_counter() - a:.3f} s, "
                     f"{compiles.count - c0} compiles, loss {m['train_loss']:.6f}")
        if r < check_rounds:
            prog["loss"].append(float(m["train_loss"]))
            prog["params"].append(system.host_params(trainer))
        r += 1
        if r >= warm and (compiles.count == c0 or r >= warm + 5):
            break
    setup_compiles, setup_compile_s = compiles.snapshot()
    inserts0 = system.data_pool_inserts(trainer)

    # --- the window
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else ""
    if trace:
        jax.profiler.start_trace(trace_dir)
    round_s: List[float] = []
    samples = 0
    losses: List[float] = []
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - t_start
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            a = time.perf_counter()
            m = trainer.run_round(r)
            system.block(trainer)
            round_s.append(time.perf_counter() - a)
            samples += fed.samples_of(fed.cohort_of(r))
            losses.append(float(m["train_loss"]))
            r += 1
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    window_compiles = compiles.count - setup_compiles
    rounds = len(round_s)
    inserts = system.data_pool_inserts(trainer) - inserts0
    device = device_info()

    e2e = {"samples_per_s": samples / window_s, "round_s_p90": p90(round_s),
           "setup_s": setup_s}
    ctx = {"bench": bench, "workload": wl, "config": config, "traffic": traffic,
           "fed": fed, "rounds": rounds, "window_s": window_s, "samples": samples,
           "samples_per_s": samples / window_s, "setup_compile_s": setup_compile_s,
           "window_compiles": window_compiles, "pool_inserts": inserts,
           "chips": int(wl["chips"]), "device_kind": device["kind"],
           "update_sizes": [int(np.prod(a.shape)) for a in
                            jax.tree_util.tree_leaves(prog["params"][-1])]}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        import devtrace as tracem

        events = tracem.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tracem.span(events, WINDOW_SPAN)
        chips = tracem.devices(events)[: ctx["chips"]]
        busy = [tracem.busy_ns(events, d, lo, hi) for d in chips]
        device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx.update(events=events, lo=lo, hi=hi, trace_chips=chips, busy_s=device["busy_s"],
                   trace_window_s=device["window_s"])
        breakdown = {"device_ops": tracem.top_ops(events, chips[0], lo, hi),
                     "idle_gaps": tracem.idle_gaps(events, chips[0], lo, hi)}
        for m in per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in end_to_end:
            metrics[m["name"]] = {"value": float(e2e[quantity(m["name"])]), "unit": m["unit"]}

    # --- the check: free the system, replay the first rounds in the reference
    del trainer, m
    if not keep_programs:
        kept = system.release()
        if kept:
            log(t_start, f"{kept} leaves of the frozen base outlived the program's caches; "
                         f"deleted from the device")
    gc.collect()
    log(t_start, f"window: {rounds} rounds in {window_s:.3f} s, {window_compiles} compiles; "
                 f"reference starts")
    want = (reference_of() if reference_of is not None else
            refm.run_reference(ref, config, fed, traffic, seed, check_rounds))
    log(t_start, "reference done")
    numbers = refm.compare(prog, want)
    for line in refm.worst_leaves(prog, want):
        log(t_start, line)
    failed = sum(1 for v in losses if not math.isfinite(v))
    for k, v in numbers.items():
        if k not in limits:
            log(t_start, f"not compared: {k} {v!r}")
    checks, within = judge(numbers, limits)
    correct = failed == 0 and rounds > 0 and within
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": rounds, "failed": failed,
                           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out, {"numbers": numbers, "leaves": refm.leaf_table(prog, want)}


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = _parse(argv)
    try:
        bench = Bench()
        bench.workload(args.workload)
    except (SpecError, OSError, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        out, _ = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except SystemExit as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
