import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) from
# ShapeDtypeStructs only — no parameter allocation.  MUST be run as its own
# process (the two lines above must execute before any jax import anywhere).
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-moe-30b-a3b \
#       --shape train_4k --multi-pod --step fed
#
# Writes artifacts/dryrun/<arch>__<shape>__<mesh>__<step>__<preset>.json with
# memory_analysis / cost_analysis / collective stats for §Dry-run + §Roofline.

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch, list_archs
from repro.configs.shapes import SHAPES, get_shape
from repro.core.federated import (
    FedRoundConfig, fed_input_specs, make_fed_round_step,
)
from repro.launch import shardings as shr
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import Roofline, model_flops
from repro.models.layers import partition_specs
from repro.models.model import (
    Model, TrainState, make_prefill_step, make_serve_step, make_train_step,
)
from repro.optim import sgd


def _mesh_shape_dict(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def build_state_specs(model: Model, optimizer, mesh, rules):
    """(abstract TrainState, NamedSharding tree) without allocation."""
    params_abs = model.abstract()
    params_pspec = partition_specs(model.defs(), rules, _mesh_shape_dict(mesh))

    def _init(params):
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    state_abs = jax.eval_shape(_init, params_abs)
    # optimizer state mirrors params (sgd momentum / adam mu,nu) — reuse the
    # params specs where the leaf count matches a whole params-tree multiple
    flat_p = jax.tree_util.tree_flatten(params_pspec)[0]
    flat_o, tdo = jax.tree_util.tree_flatten(
        state_abs.opt_state,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    if flat_o and len(flat_o) % len(flat_p) == 0:
        opt_flat = []
        for i, leaf in enumerate(flat_o):
            cand = flat_p[i % len(flat_p)]
            # scalar leaves (adam count) replicate
            opt_flat.append(cand if getattr(leaf, "shape", ()) else P())
        opt_pspec = jax.tree_util.tree_unflatten(tdo, opt_flat)
    else:
        opt_pspec = jax.tree_util.tree_unflatten(tdo, [P()] * len(flat_o))
    state_pspec = TrainState(params_pspec, opt_pspec, P())
    shard = jax.tree_util.tree_map(
        lambda s, sp: NamedSharding(mesh, sp if isinstance(sp, P) else P()),
        state_abs, state_pspec)
    return state_abs, shard, params_pspec


def batch_shardings(model: Model, specs, mesh, rules):
    axes = shr.batch_axes_for(specs)
    return shr.specs_to_shardings(specs, axes, rules, mesh)


def cache_shardings(model: Model, cache_specs, mesh, rules):
    axes = shr.cache_axes_for(cache_specs, model.cfg)
    return shr.specs_to_shardings(cache_specs, axes, rules, mesh)


def _bytes_per_device(abstract_tree, sharding_tree) -> int:
    total = 0
    leaves_a = jax.tree_util.tree_leaves(abstract_tree)
    leaves_s = jax.tree_util.tree_leaves(
        sharding_tree, is_leaf=lambda x: isinstance(x, NamedSharding))
    for a, s in zip(leaves_a, leaves_s):
        n = a.size * a.dtype.itemsize
        try:
            shard_shape = s.shard_shape(a.shape)
            sn = 1
            for d in shard_shape:
                sn *= d
            total += sn * a.dtype.itemsize
        except Exception:
            total += n
    return total


def dryrun(arch: str, shape_name: str, multi_pod: bool = False,
           step: str = "auto", preset: str = "fsdp_tp",
           fed_local_steps: int = 4, fed_compression: str = "none",
           out_dir: str = "artifacts/dryrun", seq_override: int = 0,
           extra_tag: str = "") -> dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    if seq_override:
        shape = dataclasses.replace(shape, seq_len=seq_override)
    model = Model(cfg)
    rules = dict(shr.PRESETS[preset])
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    optimizer = sgd(0.01, momentum=0.9)   # paper default optimizer

    if shape.kind == "decode" and not cfg.supports_long_context \
            and shape.seq_len > 65_536:
        return {"skipped": True, "reason": "long-context unsupported "
                "(full-attention enc-dec; DESIGN.md §4)", "arch": arch,
                "shape": shape_name}

    if step == "auto":
        step = {"train": "train", "prefill": "prefill",
                "decode": "serve"}[shape.kind]

    t0 = time.time()
    with jax.set_mesh(mesh):
        if step == "train":
            state_abs, state_shard, _ = build_state_specs(
                model, optimizer, mesh, rules)
            specs = model.input_specs(shape)
            b_shard = batch_shardings(model, specs, mesh, rules)
            fn = make_train_step(model, optimizer, remat=True)
            jitted = jax.jit(fn, in_shardings=(state_shard, b_shard),
                             out_shardings=(state_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, specs)
        elif step == "fed":
            assert multi_pod, "fed round is the multi-pod technique"
            num_pods = mesh.devices.shape[0]
            fed_cfg = FedRoundConfig(local_steps=fed_local_steps,
                                     compression=fed_compression)
            state_abs, state_shard, params_pspec = build_state_specs(
                model, optimizer, mesh, rules)

            def prepend_pod(sp):
                parts = tuple(sp) if isinstance(sp, P) else ()
                return P("pod", *parts)

            pod_state_abs = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((num_pods,) + s.shape, s.dtype),
                state_abs)
            pod_state_shard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, prepend_pod(s.spec)),
                state_shard)
            residual_abs = ()
            residual_shard = ()
            if fed_compression == "int8_sync":
                residual_abs = jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                    pod_state_abs.params)
                residual_shard = jax.tree_util.tree_map(
                    lambda s: s, pod_state_shard.params)
            elif fed_compression != "none":
                residual_abs = jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                    state_abs.params)
                residual_shard = jax.tree_util.tree_map(
                    lambda s: s, state_shard.params)
            from repro.core.federated import FedState
            fed_abs = FedState(pod_state_abs, residual_abs)
            fed_shard = FedState(pod_state_shard, residual_shard)
            specs = fed_input_specs(model, shape, num_pods, fed_cfg)
            def fed_batch_axes(s):
                return ("pod_batch",) + (None,) * (len(s.shape) - 1)
            rules_fed = dict(rules)
            rules_fed["pod_batch"] = ("pod",)
            axes = jax.tree_util.tree_map(
                fed_batch_axes, specs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            b_shard = shr.specs_to_shardings(specs, axes, rules_fed, mesh)
            fn = make_fed_round_step(model, optimizer, fed_cfg, num_pods,
                                     params_pspec=params_pspec)
            jitted = jax.jit(fn, in_shardings=(fed_shard, b_shard),
                             out_shardings=(fed_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(fed_abs, specs)
        elif step == "prefill":
            params_abs = model.abstract()
            params_pspec = partition_specs(model.defs(), rules,
                                           _mesh_shape_dict(mesh))
            p_shard = jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), params_pspec)
            specs = model.input_specs(shape)
            b_shard = batch_shardings(model, specs, mesh, rules)
            fn = make_prefill_step(model)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_abs, specs)
        elif step == "serve":
            params_abs = model.abstract()
            params_pspec = partition_specs(model.defs(), rules,
                                           _mesh_shape_dict(mesh))
            p_shard = jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp), params_pspec)
            specs = model.input_specs(shape)
            ring = shape.seq_len > 65_536
            c_shard = cache_shardings(model, specs["cache"], mesh, rules)
            tok_shard = shr.specs_to_shardings(
                {"tokens": specs["tokens"]},
                {"tokens": ("batch", None)}, rules, mesh)["tokens"]
            pos_shard = NamedSharding(mesh, P())
            fn = make_serve_step(model, ring=ring)
            jitted = jax.jit(fn,
                             in_shardings=(p_shard, c_shard, tok_shard,
                                           pos_shard),
                             out_shardings=(None, c_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, specs["cache"],
                                   specs["tokens"], specs["pos"])
        else:
            raise ValueError(step)

        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

    cost = compiled.cost_analysis() or {}
    # older jaxlibs return a one-element list of dicts
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    hc = analyze_hlo(hlo, pod_size=256 if multi_pod else None)
    if os.environ.get("REPRO_DUMP_DOTS"):
        from repro.launch.hlo_analysis import dot_breakdown
        for label, fl, m in dot_breakdown(hlo):
            print(f"  DOT {fl:.3e} flops x{m:.0f}  {label[:140]}")

    mf = model_flops(cfg, shape, text_len=model.text_len(shape))
    rl = Roofline(flops=hc.flops, hbm_bytes=hc.hbm_bytes,
                  collective_bytes=hc.collective_bytes, chips=chips,
                  model_flops=mf, dcn_bytes=hc.dcn_bytes)

    record = {
        "arch": arch,
        "shape": shape_name,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": list(mesh.devices.shape),
        "step": step,
        "preset": preset,
        "lower_s": lower_s,
        "compile_s": compile_s,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and "bytes" not in k
                          or k in ("flops", "bytes accessed")},
        "memory_analysis": mem_info,
        "collectives": {
            "bytes_by_op": hc.collective_by_op,
            "count_by_op": hc.collective_counts,
            "total_bytes": hc.collective_bytes,
        },
        "hlo_analysis": {
            "flops": hc.flops,
            "hbm_bytes": hc.hbm_bytes,
            "dot_count": hc.dot_count,
            "while_trips": hc.while_trips,
        },
        "roofline": rl.to_dict(),
        "hlo_lines": hlo.count("\n"),
    }
    if fed_compression != "none":
        record["fed_compression"] = fed_compression

    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{extra_tag}" if extra_tag else ""
    fname = (f"{arch}__{shape_name}__{record['mesh']}__{step}__{preset}"
             f"{tag}.json")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs() + ["all"])
    ap.add_argument("--shape", required=True,
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--step", default="auto",
                    choices=["auto", "train", "prefill", "serve", "fed"])
    ap.add_argument("--preset", default="fsdp_tp",
                    choices=list(shr.PRESETS))
    ap.add_argument("--fed-local-steps", type=int, default=4)
    ap.add_argument("--fed-compression", default="none",
                    choices=["none", "stc", "int8", "int8_sync"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--seq-override", type=int, default=0)
    ap.add_argument("--moe-impl", default="global",
                    choices=["global", "expert_parallel"])
    args = ap.parse_args()

    if args.moe_impl != "global":
        from repro.models import moe as _moe
        _moe.set_moe_impl(args.moe_impl)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failed = 0
    for a in archs:
        for s in shapes:
            try:
                rec = dryrun(a, s, multi_pod=args.multi_pod, step=args.step,
                             preset=args.preset,
                             fed_local_steps=args.fed_local_steps,
                             fed_compression=args.fed_compression,
                             out_dir=args.out, extra_tag=args.tag,
                             seq_override=args.seq_override)
                if rec.get("skipped"):
                    print(f"[SKIP] {a} {s}: {rec['reason']}")
                else:
                    r = rec["roofline"]
                    print(f"[OK] {a} {s} {rec['mesh']} {rec['step']} "
                          f"compile={rec['compile_s']:.1f}s "
                          f"compute={r['compute_s']:.3e}s "
                          f"memory={r['memory_s']:.3e}s "
                          f"coll={r['collective_s']:.3e}s "
                          f"dominant={r['dominant']} "
                          f"useful={r['useful_compute_ratio']:.2f}")
            except Exception:
                print(f"[FAIL] {a} {s}")
                traceback.print_exc()
                failed += 1
    if failed:
        sys.exit(f"{failed} dry run(s) failed")


if __name__ == "__main__":
    main()
