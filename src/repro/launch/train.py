"""End-to-end training driver for the assigned architectures.

Trains a (reduced or full) arch config with the production train_step on
whatever devices exist — the same code path the dry-run lowers for the
(16,16) mesh.  On a CPU host:

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --reduced \
        --steps 200 --batch 8 --seq 256

Synthetic LM data is a fixed-transition Markov stream (learnable: loss
should fall well below log(vocab)).  Checkpoints + tracking included; this
is also the driver ``examples/llm_federated.py`` builds on.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_arch, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import (
    Model, init_train_state, make_train_step,
)
from repro.optim import get_optimizer
from repro.tracking import Tracker


def synthetic_lm_batches(vocab: int, batch: int, seq: int, seed: int = 0):
    """Markov chain over a vocab-sized ring: next = cur + step (mod vocab),
    with a noisy step distribution — enough structure to verify learning."""
    rng = np.random.RandomState(seed)
    steps = rng.randint(1, 7, size=vocab)
    while True:
        start = rng.randint(0, vocab, size=(batch, 1))
        seqs = [start]
        cur = start
        for _ in range(seq - 1):
            jump = steps[cur % vocab] + (rng.rand(*cur.shape) < 0.1)
            cur = (cur + jump.astype(np.int64)) % vocab
            seqs.append(cur)
        yield {"tokens": jnp.asarray(np.concatenate(seqs, axis=1), jnp.int32)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    # size overrides on top of the reduced config (e.g. a ~100M-param run:
    # --d-model 768 --layers 12 --d-ff 2048 --vocab 32000)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    import dataclasses
    cfg = get_arch(args.arch, reduced=args.reduced)
    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
        over["n_heads"] = max(1, args.d_model // 128)
        over["n_kv_heads"] = max(1, args.d_model // 128)
        over["head_dim"] = 0
    if args.layers:
        over["n_layers"] = args.layers
    if args.d_ff:
        over["d_ff"] = args.d_ff
    if args.vocab:
        over["vocab"] = args.vocab
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = Model(cfg)
    opt = get_optimizer(args.optimizer, args.lr)
    state = init_train_state(model, opt, jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")

    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0,))
    data = synthetic_lm_batches(cfg.vocab, args.batch, args.seq, args.seed)
    tracker = Tracker()
    tracker.create_task(f"train_{cfg.name}", vars(args))

    t0 = time.perf_counter()
    losses = []
    frames = None
    if cfg.family in ("vlm", "audio"):
        frames = jnp.zeros((args.batch, cfg.n_frames, cfg.d_model),
                           jnp.dtype(cfg.dtype))
    for step in range(args.steps):
        batch = next(data)
        if frames is not None:
            batch["frames"] = frames
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            dt = time.perf_counter() - t0
            avg = float(np.mean(losses[-args.log_every:]))
            print(f"step {step+1:5d} loss {avg:.4f} "
                  f"({dt/ (step+1):.3f}s/step)")
            tracker.track_round(f"train_{cfg.name}", step, loss=avg,
                                sec_per_step=dt / (step + 1))
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, jax.device_get(state.params),
                               args.steps)
        print("checkpoint:", path)
    first = float(np.mean(losses[:10]))
    last = float(np.mean(losses[-10:]))
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'LEARNED' if last < first - 0.2 else 'check lr/steps'})")
    return losses


if __name__ == "__main__":
    main()
