"""Readings that the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <name> --seeds 11,12,13 \
        --what sound,control,half_cohort --fault-seeds 3

One line per seed and reading, with every number the check can compare,
whether it is within the cell's limits, and the per-leaf norms it comes
from.  ``sound``: a short run of the cell as it is (the lower readings).
``control``: the control (the reference with its clients in bfloat16)
against the reference in float32.  ``<fault>``: a short run with that
fault planted in the program (``lib/control.py``).  The control and the
faults run on the first ``--fault-seeds`` seeds only.  Everything runs in
one process, so the programs compile once, and each seed's float32
reference is made once for all its readings.  The benchmark's own runs
plant nothing.
"""
import argparse
import contextlib
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "lib"))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "src"))


def main() -> int:
    import control
    import harness
    import reference as refm
    import traffic as tr
    from spec import Bench

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", required=True,
                   help="comma-separated: sound, control, " + ", ".join(sorted(control.FAULTS)))
    p.add_argument("--fault-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=1.0)
    a = p.parse_args()
    whats = a.what.split(",")
    unknown = set(whats) - {"sound", "control", *control.FAULTS}
    if unknown:
        p.error(f"unknown readings {sorted(unknown)}")
    bench = Bench()
    wl = bench.workload(a.workload)
    harness.require_chips(int(wl["chips"]))
    harness.enable_compile_cache(bench.root)
    config, ref = bench.config(wl["config"]), bench.reference(wl["config"])
    traffic, limits = bench.traffic(wl["traffic"]), bench.limits(a.workload)
    rounds = int(limits["rounds"])
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        fed = tr.Federation(traffic, config, seed)
        cached = {}

        def want():
            if "f32" not in cached:
                cached["f32"] = refm.run_reference(ref, config, fed, traffic, seed, rounds)
            return cached["f32"]

        for what in whats:
            if what != "sound" and i >= a.fault_seeds:
                continue
            t0 = time.perf_counter()
            try:
                if what == "control":
                    check = control.control_check(bench, a.workload, seed, want())
                    correct = harness.judge(check["numbers"], limits)[1]
                else:
                    with control.FAULTS.get(what, contextlib.nullcontext)():
                        out, check = harness.run_cell(bench, a.workload, seed, a.seconds,
                                                      False, time.perf_counter(),
                                                      reference_of=want, keep_programs=True)
                    correct = out["correct"]
            except Exception as e:  # a reading that crashes gives no number
                check, correct = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
            print(json.dumps({"workload": a.workload, "seed": seed, "what": what,
                              "correct": correct, "s": time.perf_counter() - t0, **check}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
