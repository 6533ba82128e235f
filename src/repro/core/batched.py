"""Batched client execution engine: all selected clients in one jitted program.

The sequential runtime (``core/rounds.py``) dispatches one jitted train step
per client per batch from Python, so per-round wall time scales linearly
with cohort size N — dominated by dispatch overhead at simulation scale.
This engine stacks the selected clients' params / opt-states / cyclic-batch
indices into leading-client-dim pytrees and runs all E local epochs of the
whole cohort as **one** compiled program: ``jax.vmap`` over clients around a
``jax.lax.scan`` over local steps (the FLGo-style vectorized multi-client
simulation).

Under ``client.finetune = "lora"`` the cohort's stacked leaves are the
low-rank adapter factors only — ``(N, d_in, r)`` / ``(N, r, d_out)``
(plus a leading layers axis for scan-stacked segments) — while the frozen
base weights are closure constants of the wrapped model's ``apply``,
hoisted ONCE into the compiled program and shared by every vmapped
client.  Nothing below knows about LoRA: aggregation, in-program
compression, EF residuals and byte accounting all just see a smaller
stacked tree (``repro.models.lora``).

Shape discipline (no per-round recompiles):

* cohort size N, per-client step count S, and per-client sample count are
  each padded up to power-of-two *buckets*; the compile cache is keyed by
  ``(N_bucket, S_bucket, batch_shape)`` via the inner ``jax.jit``.
* padded clients run 0 active steps and are discarded; padded steps are
  masked out (params/opt-state frozen once ``step >= n_steps[client]``), so
  results are bit-equivalent to running each client alone.

Per-client FedProx (``proximal_mu``), gradient clipping
(``max_grad_norm``) and the full optimizer hyperparameter set ride along
as traced (N,) vectors gathered into one :class:`CohortVectors` struct:
SGD cohorts vectorize lr / momentum / weight_decay / nesterov, AdamW
cohorts lr / b1 / b2 / eps / weight_decay
(``repro.optim.sgd_traced`` / ``adamw_traced`` — hyperparams are traced
scalars threaded through ``update`` instead of Python closure constants).
Opt-state is already vmapped per client, so per-client scalars broadcast
exactly; a heterogeneous cohort matches per-client sequential execution
(bit-for-bit for SGD, ulp-level for AdamW's ``1-beta`` arithmetic).  Only
mixed optimizer *families* (sgd vs adamw) cannot share one program and
raise loudly, naming the offending clients.  ``FedAvg``, ``FedProx`` and
``STC`` strategies all share one program (STC only changes the post-train
compression stage, which stays on the per-client Python path).  The
stacked initial params are donated to the program — XLA reuses the
cohort-sized buffer for the evolving local params.

The virtual clock changes meaning here: wall time is shared by the whole
cohort, so per-client base times are derived from each client's step count
scaled by the measured per-step cost of the batched program; the
system-heterogeneity simulator and GreedyAda makespan (Eq. 1) consume those
exactly as before.

Device-mesh sharding (``resources.distributed = "data"``): the stacked
client dimension is additionally sharded over a 1-D ``jax.sharding.Mesh``
of the local devices (axis ``"clients"``) via ``NamedSharding`` on the
jitted program's inputs/outputs — global params replicated, client
data / batch indices / evolving local params sharded.  Because the cohort
is bucket-padded to a power of two (and at least the mesh size), shards
stay equal-sized and one compiled program serves every round.  Each
client's local training is independent, so the program runs without any
cross-device collective; communication happens only at aggregation, where
``kernels.fedavg_agg.fedavg_aggregate_sharded`` reduces per-shard partial
weighted sums with a ``psum`` epilogue instead of gathering all N updates
to one device.

Virtual-clock semantics under sharding are unchanged: the measured wall
time is the synchronous dispatch of the whole (sharded) cohort program —
the makespan over shards — and per-client base times remain each client's
step-count share of that wall time.  Shard placement is an *implementation*
detail of the simulator host, not part of the simulated federation, so the
heterogeneity simulator and GreedyAda see exactly the same inputs as the
unsharded batched path.

In-program compression (the paper's flagship STC plugin, §V-B, on the
fast path): :meth:`BatchedExecutor.compress_stacked` sparsifies (STC) or
quantizes (int8) the stacked cohort update with batched 2-D-grid Pallas
kernels — per shard of the client mesh when distributed — with
error-feedback residuals held in a device-resident per-client-id store,
so compressed rounds keep the no-gather pipeline (compress → aggregate
entirely on device) and wire sizes come from the kernels' per-client nnz.
Round-over-round residual semantics match ``Client._residual`` exactly,
including across async dispatch waves.  The cohort *data* (x/y) comes
from a device-resident per-client pool
(:meth:`BatchedExecutor._stacked_data`): each client's padded rows upload
host→device once, cohorts assemble by a device row gather regardless of
selection order/composition, and only the shuffled batch indices are
rebuilt per round.
"""
from __future__ import annotations

import time
import warnings
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from typing import NamedTuple

from repro.core.local_train import cyclic_batches
from repro.models.small import FLModel
from repro.optim import (
    Optimizer, TracedOptimizer, adamw_traced, apply_updates, global_norm,
    hparams_from_config, sgd_traced,
)

PyTree = Any

CLIENT_AXIS = "clients"
# FedAvg's weighted sums stay exact f32 on every backend (the TPU's default
# f32 matmul rounds its operands to bf16)
HIGHEST = jax.lax.Precision.HIGHEST


class CohortVectors(NamedTuple):
    """All per-client (N_bucket,) vectors of the cohort program, in one
    struct: the FedProx proximal coefficient, the grad-clip threshold, and
    the optimizer hyperparameter struct (``SGDHParams`` / ``AdamWHParams``
    of (N_bucket,) vectors — or ``()`` when the cohort shares one
    hand-built uniform :class:`Optimizer` instance).

    This is the single vector path into the jitted program — strategies
    that need a new per-client scalar (FedProx's ``mu`` did, per-client
    optimizer hyperparams do now) extend this struct instead of growing
    the program signature ad hoc."""

    mu: Any
    max_norm: Any
    hp: Any


_trace_count = 0
_round_traces = 0
_dispatches = 0
_host_syncs = 0


def cohort_trace_count() -> int:
    """How many times a cohort program has been (re)traced this process.

    The program body executes exactly once per jit trace (= compile), so
    tests and benchmarks assert zero round-over-round recompiles at fixed
    bucket shapes by checking this counter stays flat across rounds."""
    return _trace_count


def round_trace_count() -> int:
    """How many times a fused *round* program (:func:`make_round_program`)
    has been (re)traced this process — the fused-path analogue of
    :func:`cohort_trace_count`; flat across rounds at fixed bucket shapes
    (asserted by ``flcheck --contracts``)."""
    return _round_traces


def dispatch_count() -> int:
    """Executor-level program dispatches this process.

    Counts each *stage* the batched engine hands to the device — cohort
    training, in-program compression, aggregation, server apply — not
    individual XLA ops, so the staged count is a lower bound on real
    dispatch traffic while the fused round is exactly 1.  Benchmarks and
    ``flcheck --contracts`` assert the fused round's delta is 1."""
    return _dispatches


def host_sync_count() -> int:
    """Device->host synchronization points (blocking fetches) this process.

    Each ``block_until_ready`` / ``device_get`` the round pipeline performs
    bumps this once; the fused round performs exactly one batched fetch."""
    return _host_syncs


def _note_dispatch(n: int = 1) -> None:
    global _dispatches
    _dispatches += n


def _note_host_sync(n: int = 1) -> None:
    global _host_syncs
    _host_syncs += n


@lru_cache(maxsize=32)
def _wrap_uniform(optimizer: Optimizer) -> TracedOptimizer:
    """Adapt a hand-built, cohort-uniform closure :class:`Optimizer` to the
    traced interface (hyperparam struct ignored — it is ``()``)."""
    return TracedOptimizer(
        init=lambda p, hp: optimizer.init(p),
        update=lambda g, s, p, hp: optimizer.update(g, s, p),
        name=f"uniform({optimizer.name})")


def bucket_pow2(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    b = max(1, floor)
    while b < n:
        b *= 2
    return b


def build_client_mesh(devices: Optional[Sequence] = None):
    """1-D mesh over the largest power-of-two prefix of ``devices``.

    The client dimension is bucket-padded to powers of two, so a
    power-of-two mesh always divides it evenly.  Raises ``ValueError`` when
    no devices are available (the loud failure mode for
    ``resources.distributed="data"`` on a mesh-less host).
    """
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if not devices:
        raise ValueError(
            'resources.distributed="data" needs at least one jax device to '
            "build the client mesh, but none are available")
    n = 1
    while n * 2 <= len(devices):
        n *= 2
    if n < len(devices):
        warnings.warn(
            f"client mesh uses {n} of {len(devices)} devices (largest "
            f"power of two); {len(devices) - n} device(s) stay idle",
            stacklevel=2)
    return Mesh(np.asarray(devices[:n]), (CLIENT_AXIS,))


def _one_client_fn(model: FLModel, optimizer: TracedOptimizer, steps: int,
                   use_prox: bool, use_clip: bool):
    """Single-client local-training body shared by the staged cohort
    program (:func:`make_cohort_program`) and the fused round program
    (:func:`make_round_program`), so both paths trace byte-identical
    training arithmetic."""

    def one_client(params, x, y, idx, n_steps, vec, global_params):
        global _trace_count
        _trace_count += 1            # executes once per jit trace/compile
        opt_state = optimizer.init(params, vec.hp)

        def body(carry, xs):
            params, opt_state, loss_sum, acc_sum = carry
            step, bidx = xs
            batch = {"x": x[bidx], "y": y[bidx]}

            def loss_fn(p):
                loss, metrics = model.loss_and_metrics(p, batch)
                if use_prox:
                    prox = sum(
                        jnp.sum(jnp.square(a.astype(jnp.float32)
                                           - g.astype(jnp.float32)))
                        for a, g in zip(jax.tree_util.tree_leaves(p),
                                        jax.tree_util.tree_leaves(global_params)))
                    loss = loss + 0.5 * vec.mu * prox
                return loss, metrics

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if use_clip:
                norm = global_norm(grads)
                scale = jnp.where(
                    vec.max_norm > 0.0,
                    jnp.minimum(1.0, vec.max_norm / (norm + 1e-9)), 1.0)
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            updates, new_opt = optimizer.update(grads, opt_state, params,
                                                vec.hp)
            new_params = apply_updates(params, updates)

            active = step < n_steps          # padded steps leave state frozen
            params = jax.tree_util.tree_map(
                lambda nw, od: jnp.where(active, nw, od), new_params, params)
            opt_state = jax.tree_util.tree_map(
                lambda nw, od: jnp.where(active, nw, od), new_opt, opt_state)
            af = active.astype(jnp.float32)
            loss_sum = loss_sum + af * loss
            acc_sum = acc_sum + af * metrics.get("accuracy", jnp.float32(0))
            return (params, opt_state, loss_sum, acc_sum), None

        (params, _, loss_sum, acc_sum), _ = jax.lax.scan(
            body,
            (params, opt_state, jnp.float32(0), jnp.float32(0)),
            (jnp.arange(steps), idx))
        update = jax.tree_util.tree_map(
            lambda n, g: n.astype(jnp.float32) - g.astype(jnp.float32),
            params, global_params)
        denom = jnp.maximum(n_steps.astype(jnp.float32), 1.0)
        return update, loss_sum / denom, acc_sum / denom

    return one_client


@lru_cache(maxsize=32)
def make_cohort_program(model: FLModel, optimizer: TracedOptimizer,
                        steps: int, use_prox: bool, use_clip: bool,
                        mesh=None):
    """One jitted program running ``steps`` local steps for a whole cohort.

    Signature of the returned function (leading dim N_bucket everywhere
    except ``global_params``):

        (params, x, y, idx, n_steps, vec, global_params)
            -> (updates, loss_mean, acc_mean)

    ``vec`` is a :class:`CohortVectors`: the per-client FedProx ``mu``,
    grad-clip ``max_norm`` and the optimizer hyperparameter struct, each
    leaf an (N_bucket,) vector vmapped down to a per-client scalar.
    ``optimizer`` is a :class:`repro.optim.TracedOptimizer` whose
    ``init``/``update`` consume ``vec.hp`` — per-client opt-state is
    already vmapped, so per-client hyperparameter scalars broadcast
    exactly and heterogeneous momentum / weight decay / nesterov / betas
    need no special casing.

    ``params`` (the stacked copies of the global model) is donated.
    With ``mesh`` (1-D, axis "clients"), every leading-client-dim argument
    and output is given a ``NamedSharding`` over the mesh and
    ``global_params`` is replicated, so the cohort streams through all
    devices; N_bucket must be a multiple of the mesh size.
    """
    one_client = _one_client_fn(model, optimizer, steps, use_prox, use_clip)
    batched = jax.vmap(one_client,
                       in_axes=(0, 0, 0, 0, 0, 0, None))
    if mesh is None:
        return jax.jit(batched, donate_argnums=(0,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    cl = NamedSharding(mesh, P(CLIENT_AXIS))   # shard the leading client dim
    rep = NamedSharding(mesh, P())             # replicate
    return jax.jit(batched,
                   in_shardings=(cl, cl, cl, cl, cl, cl, rep),
                   out_shardings=(cl, cl, cl),
                   donate_argnums=(0,))


def _server_step_fn(method: str, stc_sparsity: float, use_faults: bool,
                    max_update_norm: float, topology: str, fanout: int,
                    use_kernel: bool, server_lr: float,
                    interpret: Optional[bool], mesh):
    """Server half of the fused round program (:func:`make_round_program`):
    compression with the error-feedback residual update, the fault guard,
    FedAvg and the apply, on stacked client updates.

        (global_params, updates, weights, mask, nan_mask, ef_leaves,
         ef_rows) -> (new_global_params, guard_ok, nnz, new_ef_leaves)

    Op for op the staged ``compress_stacked`` + ``aggregate_stacked`` +
    ``aggregation.apply_delta``; kept apart from the training so the two
    paths can be compared on the same updates."""
    tree = topology == "hierarchical"

    def server_step(global_params, updates, weights, mask, nan_mask,
                    ef_leaves, ef_rows):
        from repro.core.compression import DENSE_MIN_ELEMS
        from repro.core.tiered_store import set_rows
        from repro.kernels import ops as kops
        from repro.kernels.fedavg_agg import (fedavg_aggregate_sharded,
                                              fedavg_aggregate_tree)

        leaves, treedef = jax.tree_util.tree_flatten(updates)
        nb = leaves[0].shape[0]
        flat_leaves, nnz_list, new_ef = [], [], []
        for li, leaf in enumerate(leaves):
            size = int(np.prod(leaf.shape[1:], dtype=np.int64))
            flat = leaf.reshape(nb, size).astype(jnp.float32)
            if method != "none":
                # error-correct by the stored residual; padded clients
                # (row sentinel = alloc) gather 0 / write nowhere, so
                # semantics match the staged compress_stacked exactly
                res = jnp.take(ef_leaves[li], ef_rows, axis=0,
                               mode="fill", fill_value=0.0)
                corrected = flat + res
                if size < DENSE_MIN_ELEMS:   # tiny tensors stay dense
                    sent = corrected
                elif method == "stc":
                    sent, nnz = kops.stc_compress_batched(
                        corrected, stc_sparsity, interpret=interpret,
                        mesh=mesh)
                    nnz_list.append(nnz)
                else:
                    sent, _ = kops.int8_roundtrip_batched(
                        corrected, interpret=interpret, mesh=mesh)
                new_ef.append(set_rows(ef_leaves[li], ef_rows,
                                       corrected - sent))
                flat = sent
            flat_leaves.append(flat)
        flat = (flat_leaves[0] if len(flat_leaves) == 1
                else jnp.concatenate(flat_leaves, axis=1))

        if use_faults:
            # identical op order to aggregate_stacked's fault branch:
            # poison AFTER compression, guard on the sent values, zero
            # rejected rows in the data, renormalize the survivors
            flat = jnp.where(nan_mask[:, None], jnp.float32(jnp.nan), flat)
            wj = weights * mask
            ok = jnp.isfinite(flat).all(axis=1)
            if max_update_norm > 0:
                norms = jnp.sqrt(jnp.sum(
                    jnp.square(flat.astype(jnp.float32)), axis=1))
                ok = ok & (norms <= max_update_norm)
            wj = wj * ok.astype(jnp.float32)
            flat = jnp.where(ok[:, None], flat, 0.0)
            wsum = jnp.sum(wj)
            w = jnp.where(wsum > 0, wj / wsum, 0.0)
        else:
            ok = jnp.ones((nb,), bool)
            w = weights

        if mesh is not None:
            delta = fedavg_aggregate_sharded(
                flat, w, mesh, interpret=interpret,
                fanout=(fanout or int(np.ceil(np.sqrt(nb)))) if tree else 0)
        elif tree:
            delta = fedavg_aggregate_tree(
                flat, w, fanout=fanout, use_kernel=use_kernel,
                interpret=interpret)
        elif use_kernel:
            delta = kops.fedavg_aggregate(flat, w, interpret=interpret)
        else:
            delta = jnp.einsum("n,nd->d", w, flat.astype(jnp.float32),
                               precision=HIGHEST)

        out, off = [], 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape[1:], dtype=np.int64))
            out.append(delta[off: off + size].reshape(leaf.shape[1:]))
            off += size
        delta_tree = jax.tree_util.tree_unflatten(treedef, out)
        # the server apply (aggregation.apply_delta), in-program
        new_global = jax.tree_util.tree_map(
            lambda p, d: (p.astype(jnp.float32)
                          + server_lr * d).astype(p.dtype),
            global_params, delta_tree)
        return new_global, ok, tuple(nnz_list), tuple(new_ef)

    return server_step


@lru_cache(maxsize=16)
def make_round_program(model: FLModel, optimizer: TracedOptimizer,
                       steps: int, use_prox: bool, use_clip: bool,
                       method: str = "none", stc_sparsity: float = 0.01,
                       use_faults: bool = False,
                       max_update_norm: float = 0.0, topology: str = "flat",
                       fanout: int = 0, use_kernel: bool = False,
                       server_lr: float = 1.0,
                       interpret: Optional[bool] = None,
                       mesh=None):
    """ONE jitted program for the whole round (``resources.round_fusion``).

    Fuses cohort training (the shared :func:`_one_client_fn` body —
    byte-identical arithmetic to the staged path), in-program STC / int8
    compression with the error-feedback residual update, fault mask /
    NaN-guard / survivor renormalization, flat-or-hierarchical streaming
    FedAvg, and the server ``apply_delta`` into a single dispatch.
    Signature of the returned function (N_b = bucketed cohort dim):

        (global_params, x, y, idx, n_steps, vec, weights, mask, nan_mask,
         ef_leaves, ef_rows)
            -> (new_global_params, loss, acc, guard_ok, nnz, new_ef_leaves)

    * ``weights`` — (N_b,) f32 normalized FedAvg weights (0 beyond N);
      traced, so round-over-round cohort composition never retraces.
    * ``mask`` / ``nan_mask`` — (N_b,) fault survival mask (f32 0/1) and
      post-compression NaN-poisoning rows (bool); both traced and only
      consulted when the static ``use_faults`` is True, so a fault-free
      build stays byte-identical to the plain fused program.
    * ``ef_leaves`` / ``ef_rows`` — the EF residual store's hot-tier
      ``(alloc, leaf_size)`` matrices plus each client's row index
      (``alloc`` = out-of-bounds sentinel for padded clients: gathers
      fill 0, scatters drop), updated in-program and returned; ``()`` and
      ignored under ``method="none"``.
    * ``nnz`` — per-STC-leaf (N_b,) non-zero counts for wire accounting
      (empty tuple otherwise); fetched by the caller in the round's ONE
      batched device->host transfer together with loss/acc/guard_ok.

    ``global_params`` and ``ef_leaves`` are donated (XLA reuses the param
    buffer for ``params + server_lr * delta`` and the residual matrices
    in place; CPU declines donation, and callers must not reuse the old
    references afterwards).  With ``mesh``, client-dim arguments shard
    over the client axis, params replicate, and aggregation runs the
    per-shard partial-sum + ``psum`` kernel — all inside the same
    program.
    """
    one_client = _one_client_fn(model, optimizer, steps, use_prox, use_clip)
    batched = jax.vmap(one_client, in_axes=(0, 0, 0, 0, 0, 0, None))
    server_step = _server_step_fn(method, stc_sparsity, use_faults,
                                  max_update_norm, topology, fanout,
                                  use_kernel, server_lr, interpret, mesh)

    def round_fn(global_params, x, y, idx, n_steps, vec, weights, mask,
                 nan_mask, ef_leaves, ef_rows):
        global _round_traces
        _round_traces += 1           # executes once per jit trace/compile
        nb = x.shape[0]
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (nb,) + p.shape),
            global_params)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            stacked = jax.lax.with_sharding_constraint(
                stacked, NamedSharding(mesh, P(CLIENT_AXIS)))
        updates, loss, acc = batched(stacked, x, y, idx, n_steps, vec,
                                     global_params)
        new_global, ok, nnz, new_ef = server_step(
            global_params, updates, weights, mask, nan_mask, ef_leaves,
            ef_rows)
        return (new_global, loss, acc, ok, nnz, new_ef)

    if mesh is None:
        return jax.jit(round_fn, donate_argnums=(0, 9))
    from jax.sharding import NamedSharding, PartitionSpec as P

    cl = NamedSharding(mesh, P(CLIENT_AXIS))
    rep = NamedSharding(mesh, P())
    ef = NamedSharding(mesh, P(CLIENT_AXIS, None))
    return jax.jit(round_fn,
                   in_shardings=(rep, cl, cl, cl, cl, cl, rep, rep, rep,
                                 ef, rep),
                   out_shardings=(rep, cl, cl, cl, cl, ef),
                   donate_argnums=(0, 9))


class BatchedExecutor:
    """Runs a cohort of :class:`repro.core.client.Client` objects as one
    compiled program and hands back per-client result dicts shaped exactly
    like ``Client.train`` output, so the per-client compression/encryption/
    upload stages (and strategy overrides of them, e.g. STC) keep working.

    ``distributed="data"`` shards the stacked client dimension over a 1-D
    device mesh (see module docstring); ``devices`` overrides the device
    set (tests use prefixes of the host platform's forced devices to prove
    shard-count invariance).  ``max_clients`` is how many clients' data
    and error-feedback rows stay on the device (further capped by the two
    class bounds below); both tiers allocate that many rows on first use
    and spill beyond it."""

    #: bound on the *device-resident* tier of the per-client data pool
    #: (rows).  Cold clients beyond the bound are LRU-evicted and cost
    #: zero storage — their padded rows are recomputed from ``c.data``
    #: (itself regenerated on demand for virtual datasets) on the next
    #: selection.  A cohort larger than the bound pins the tier open for
    #: its round, so device memory is ``max(bound, cohort)`` rows.
    DATA_POOL_MAX_CLIENTS = 1024
    #: bound on the device-resident tier of the error-feedback residual
    #: store; evicted residuals spill to pinned host numpy copies and
    #: reload bit-identically (residuals are state, not recomputable)
    EF_MAX_CLIENTS = 1024

    def __init__(self, model: FLModel, max_clients: int,
                 distributed: str = "none",
                 devices: Optional[Sequence] = None):
        if distributed not in ("none", "data"):
            raise ValueError(
                f"unknown distributed {distributed!r}; expected 'none' or "
                f"'data'")
        self.model = model
        self.distributed = distributed
        self.mesh = (build_client_mesh(devices)
                     if distributed == "data" else None)
        # tiered per-client data pool (repro.core.tiered_store): each
        # client's (maxn, ...) padded x/y rows upload host->device once
        # while hot; cohorts are assembled by a device-side row gather, so
        # arbitrary selection order / composition (random permutations,
        # async waves) all hit the pool.  Eviction drops the row — data is
        # recomputable from ``c.data``, so the cold tier costs nothing.
        self._pool = None              # lazily-built TieredRowStore
        self._pool_maxn = 0
        self._pool_sig = None          # (x tail shape/dtype, y ditto)
        # tiered error-feedback residual store for in-program compression:
        # hot rows live in per-leaf (alloc, leaf_size) device matrices,
        # evicted rows spill to host and reload bit-identically, so
        # round-over-round semantics match ``Client._residual`` exactly —
        # including across async waves, which share this executor.
        # Both stores hold at most ``max_clients`` hot rows, allocated at
        # once, so the round program that takes the whole EF store never
        # retraces as new clients arrive
        self._ef = None                # lazily-built TieredRowStore
        if max_clients < 1:
            raise ValueError(f"max_clients must be >= 1, got {max_clients}")
        self.max_clients = int(max_clients)

    # ------------------------------------------------------------------
    @property
    def _data_pool(self) -> Optional[Dict[str, Any]]:
        """Read-only view of the pooled device data (tests/diagnostics)."""
        if self._pool is None or not self._pool.leaves:
            return None
        return {"rows": dict(self._pool.rows), "maxn": self._pool_maxn,
                "x": self._pool.leaves[0], "y": self._pool.leaves[1]}

    @property
    def _ef_rows(self) -> Dict[str, int]:
        """Hot-tier residual row map (tests/diagnostics)."""
        return dict(self._ef.rows) if self._ef is not None else {}

    # ------------------------------------------------------------------
    def _batch_indices(self, client, round_id: int) -> np.ndarray:
        """Replicates Client.train's epoch/seed schedule exactly."""
        from repro.core.client import _stable_hash
        seed = round_id * 9973 + _stable_hash(client.client_id)
        rows = [cyclic_batches(len(client.data), client._batch_size(), seed + e)
                for e in range(client.cfg.local_epochs)]
        return np.concatenate(rows).astype(np.int32)

    def _new_ef_store(self):
        from repro.core.tiered_store import TieredRowStore

        return TieredRowStore(min(self.EF_MAX_CLIENTS, self.max_clients),
                              spill="host", mesh=self.mesh, name="ef-store")

    # ------------------------------------------------------------------
    def invalidate_data(self, client_id: Optional[str] = None) -> None:
        """Drop cached device data so the next round re-reads ``c.data``.

        The pool assumes client datasets are **static** (true for every
        built-in dataset); code that swaps or mutates a client's
        ``data.x``/``data.y`` mid-run (online FL, re-partitioning) must
        call this — with the client id, or without arguments to drop the
        whole pool — or the batched/async fast path keeps training on the
        first-round snapshot."""
        if self._pool is None:
            return
        if client_id is None:
            self._pool = None
        else:
            # free the row slot; the client re-uploads on next selection
            self._pool.drop(client_id)

    # ------------------------------------------------------------------
    def _stacked_data(self, clients: Sequence, n_bucket: int, maxn: int):
        """Stacked (N_bucket, maxn, ...) cohort x/y from the tiered pool.

        Client datasets are static (see :meth:`invalidate_data` for the
        escape hatch), so each client's padded data rows are built +
        uploaded host->device only when the client is (re)admitted to the
        hot tier; while hot, every round — regardless of selection order
        or cohort composition (random permutations, async replacement
        waves) — assembles the cohort with one device-side row gather,
        and only the shuffled batch *indices* are rebuilt per round.
        Beyond ``DATA_POOL_MAX_CLIENTS`` resident clients the pool
        LRU-evicts: data rows are recomputable from ``c.data`` (and for
        virtual datasets ``c.data`` itself regenerates from the seed), so
        eviction just drops the row and cold clients cost zero storage —
        device memory stays flat as the population grows.  The pool's
        sample-dim padding grows monotonically to the bucketed federation
        max (a handful of recompiles at most).  Under the client mesh the
        gathered cohort is placed on its ``NamedSharding`` so jit never
        re-shards it."""
        from repro.core.tiered_store import TieredRowStore

        x0 = np.asarray(clients[0].data.x)
        y0 = np.asarray(clients[0].data.y)
        sig = (x0.shape[1:], x0.dtype, y0.shape[1:], y0.dtype)
        if self._pool is not None and self._pool_sig != sig:
            self._pool = None          # dataset/shape changed: reset
        if self._pool is None:
            self._pool = TieredRowStore(
                min(self.DATA_POOL_MAX_CLIENTS, self.max_clients),
                spill="drop", name="data-pool")
            self._pool_sig = sig
            self._pool_maxn = maxn
        if maxn > self._pool_maxn:
            self._pool.pad_dim1(maxn)
            self._pool_maxn = maxn
        by_id = {c.client_id: c for c in clients}
        width = self._pool_maxn

        def make_row(cid):             # recompute path: re-pad from c.data
            c = by_id[cid]
            n = len(c.data)
            nx = np.zeros((width,) + x0.shape[1:], x0.dtype)
            ny = np.zeros((width,) + y0.shape[1:], y0.dtype)
            nx[:n] = c.data.x
            ny[:n] = c.data.y
            return [nx, ny]

        xd, yd = self._pool.gather([c.client_id for c in clients], make_row)
        padn = n_bucket - len(clients)
        if padn:                       # bucket padding: all-zero rows
            xd = jnp.pad(xd, ((0, padn),) + ((0, 0),) * (xd.ndim - 1))
            yd = jnp.pad(yd, ((0, padn),) + ((0, 0),) * (yd.ndim - 1))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P(CLIENT_AXIS))
            xd, yd = jax.device_put(xd, sh), jax.device_put(yd, sh)
        return xd, yd

    # ------------------------------------------------------------------
    @staticmethod
    def _cohort_optimizer(clients: Sequence):
        """Resolve the cohort's traced optimizer + per-client hp rows.

        Every per-client optimizer hyperparameter within one family is
        vectorized: client configs are turned into per-client hyperparam
        structs (``SGDHParams`` / ``AdamWHParams``) consumed by the traced
        optimizer, so heterogeneous lr / momentum / weight decay /
        nesterov (SGD) and lr / betas / eps / weight decay (AdamW) all
        share ONE jitted program.  Static gates (``use_momentum`` /
        ``use_nesterov``) prune dead state when the whole cohort sits on
        the trivial value, so an lr-only or fully uniform cohort compiles
        the same lean program as before.

        Two cases cannot be vectorized and raise ``ValueError`` naming the
        offending clients: mixed optimizer *families* (sgd vs adamw —
        different update rules and opt-state shapes), and per-client
        hand-assigned optimizer objects that don't match the client
        configs (a cohort-wide *uniform* hand-built instance is still
        honored via a traced wrapper).
        """
        from repro.optim import get_optimizer

        # Name equality, not object identity: the name encodes every
        # hyperparameter, so it identifies a config-derived optimizer even
        # after get_optimizer's lru cache evicts the original instance
        # (cohorts with >128 distinct hyperparam combos), and a hand-built
        # optimizer that *matches* its config is behaviorally from-config.
        from_cfg = all(
            c.optimizer.name == get_optimizer(
                c.cfg.optimizer, c.cfg.lr, c.cfg.momentum,
                c.cfg.weight_decay, c.cfg.nesterov, c.cfg.adam_b1,
                c.cfg.adam_b2, c.cfg.adam_eps).name
            for c in clients)
        if not from_cfg:
            if len({id(c.optimizer) for c in clients}) == 1:
                return _wrap_uniform(clients[0].optimizer), [()] * len(clients)
            raise ValueError(
                "batched execution cannot vectorize hand-assigned "
                "per-client optimizer objects "
                f"({sorted({c.optimizer.name for c in clients})}); keep "
                "optimizers in the client configs or use "
                "resources.execution='sequential'")
        families: Dict[str, List[str]] = {}
        rows = []
        for c in clients:
            family, hp = hparams_from_config(c.cfg)
            families.setdefault(family, []).append(c.client_id)
            rows.append(hp)
        if len(families) > 1:
            detail = "; ".join(f"{fam}: {ids}"
                               for fam, ids in sorted(families.items()))
            raise ValueError(
                "batched execution cannot mix optimizer families in one "
                "cohort (per-client hyperparameters within one family are "
                f"vectorized) — got {detail}; use "
                "resources.execution='sequential' or partition the "
                "federation by family")
        if "sgd" in families:
            opt = sgd_traced(
                use_momentum=any(r.momentum != 0.0 for r in rows),
                use_nesterov=any(r.nesterov for r in rows))
        else:
            opt = adamw_traced()
        return opt, rows

    # ------------------------------------------------------------------
    @staticmethod
    def cohort_vectors(clients: Sequence, n_bucket: int):
        """Build the cohort's :class:`CohortVectors` + traced optimizer.

        The one shared (N_bucket,) vector builder: FedProx ``mu``,
        grad-clip ``max_norm`` and the optimizer hyperparam struct are
        stacked from the client configs in one place, with padded rows
        filled with inert values (padded clients run 0 active steps; mu
        and max_norm pad to 0, hyperparams pad to the first client's row
        so the traced ops stay NaN-free)."""
        opt, rows = BatchedExecutor._cohort_optimizer(clients)
        n = len(clients)

        def stack(values, pad):
            a = np.full((n_bucket,), pad, np.float32)
            a[:n] = values
            return a

        mu = stack([c.cfg.proximal_mu for c in clients], 0.0)
        max_norm = stack([c.cfg.max_grad_norm for c in clients], 0.0)
        if rows[0] == ():            # cohort-uniform hand-built optimizer
            hp = ()
        else:
            hp_cls = type(rows[0])
            hp = hp_cls(*(stack([getattr(r, f) for r in rows],
                                getattr(rows[0], f))
                          for f in hp_cls._fields))
        return CohortVectors(mu=mu, max_norm=max_norm, hp=hp), opt

    # ------------------------------------------------------------------
    def _cohort_inputs(self, clients: Sequence, round_id: int):
        """Host-side round prep shared by the staged and fused paths:
        bucketed shapes, cohort vectors + traced optimizer, pooled device
        data, batch indices and per-client step counts."""
        batch_sizes = {c._batch_size() for c in clients}
        if len(batch_sizes) != 1:
            raise ValueError(
                f"batched execution needs a uniform batch size, got "
                f"{sorted(batch_sizes)}")
        B = batch_sizes.pop()

        N = len(clients)
        Nb = bucket_pow2(N)
        if self.mesh is not None:
            Nb = max(Nb, self.mesh.size)   # equal shards: mesh size divides Nb
        vec, optimizer = self.cohort_vectors(clients, Nb)
        idx_list = [self._batch_indices(c, round_id) for c in clients]
        S = bucket_pow2(max(len(ix) for ix in idx_list))
        maxn = bucket_pow2(max(len(c.data) for c in clients))

        xd, yd = self._stacked_data(clients, Nb, maxn)
        idx = np.zeros((Nb, S, B), dtype=np.int32)
        n_steps = np.zeros((Nb,), dtype=np.int32)
        for i, c in enumerate(clients):
            idx[i, : len(idx_list[i])] = idx_list[i]
            n_steps[i] = len(idx_list[i])
        return Nb, S, vec, optimizer, xd, yd, idx, n_steps

    # ------------------------------------------------------------------
    def run_cohort_stacked(self, clients: Sequence, global_params: PyTree,
                           round_id: int) -> Dict[str, Any]:
        """Train the cohort and return the *stacked* results.

        Returns a dict with ``updates`` (pytree, leading dim N_bucket —
        device-sharded over the client mesh when distributed), ``loss`` /
        ``acc`` (np arrays, (N_bucket,)), ``n_steps`` (np, (N_bucket,)),
        ``num_samples`` (np, (N,)), and ``wall`` (float seconds).  The
        distributed aggregation fast path consumes this directly so client
        updates never gather onto one device.
        """
        Nb, S, vec, optimizer, xd, yd, idx, n_steps = self._cohort_inputs(
            clients, round_id)

        program = make_cohort_program(
            self.model, optimizer, S,
            use_prox=bool((vec.mu > 0).any()),
            use_clip=bool((vec.max_norm > 0).any()),
            mesh=self.mesh)

        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (Nb,) + p.shape), global_params)
        if self.mesh is not None:
            # eager broadcast_to commits to the default device; place the
            # donated buffer on its client-dim sharding explicitly
            from jax.sharding import NamedSharding, PartitionSpec as P
            stacked = jax.device_put(
                stacked, NamedSharding(self.mesh, P(CLIENT_AXIS)))
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            # CPU backends may decline the donation; that is fine.
            warnings.filterwarnings("ignore", message=".*donated.*")
            updates, loss, acc = program(
                stacked, xd, yd, jnp.asarray(idx),
                jnp.asarray(n_steps),
                jax.tree_util.tree_map(jnp.asarray, vec), global_params)
        _note_dispatch()
        # the round's timing boundary: ``wall`` feeds the virtual clock, so
        # the program must actually have finished here
        jax.block_until_ready(updates)  # flcheck: ignore[FLC101]  -- intended timing boundary
        _note_host_sync()
        wall = time.perf_counter() - t0

        return {
            "updates": updates,
            "loss": np.asarray(loss),
            "acc": np.asarray(acc),
            "n_steps": n_steps,
            "num_samples": np.asarray([len(c.data) for c in clients],
                                      dtype=np.int64),
            "wall": wall,
        }

    # ------------------------------------------------------------------
    def run_round_fused(self, clients: Sequence, global_params: PyTree,
                        round_id: int, *, method: str = "none",
                        stc_sparsity: float = 0.01,
                        use_kernel: bool = False, topology: str = "flat",
                        fanout: int = 0, use_faults: bool = False,
                        mask: Optional[np.ndarray] = None,
                        nan_rows: Sequence[int] = (),
                        max_update_norm: float = 0.0, server_lr: float = 1.0,
                        interpret: Optional[bool] = None, sync: bool = True):
        """Run the whole round as ONE dispatch (:func:`make_round_program`).

        Returns ``(st, new_global_params, fetch)``: ``st`` is the stacked
        result dict (no ``updates`` — they are consumed in-program), and
        the round's single batched device->host transfer pulls loss / acc
        / guard_ok / per-leaf STC nnz together.  With ``sync=True`` the
        fetch has happened (``st`` holds host np arrays, ``fetch`` is
        ``None``, and ``wall`` is the blocking round time — the virtual
        clock's boundary).  With ``sync=False`` (``tracking.round_sync``)
        dispatch returns immediately: ``wall`` is submission time, ``st``
        holds device arrays and the caller runs ``fetch()`` later —
        typically after dispatching round R+1, overlapping the transfer
        with compute.  The EF residual store is updated in-program
        (state/checkpoint format unchanged); its hot-tier matrices and
        ``global_params`` are donated, so callers must drop old references
        to the incoming server params.
        """
        Nb, S, vec, optimizer, xd, yd, idx, n_steps = self._cohort_inputs(
            clients, round_id)
        from repro.core.aggregation import fedavg_weights
        from repro.kernels import ops as kops

        N = len(clients)
        num_samples = np.asarray([len(c.data) for c in clients],
                                 dtype=np.int64)
        w = np.zeros((Nb,), np.float32)
        w[:N] = fedavg_weights(num_samples)
        m = np.zeros((Nb,), np.float32)
        m[:N] = 1.0 if mask is None else np.asarray(mask, np.float32)
        nanm = np.zeros((Nb,), bool)
        if len(nan_rows):
            nanm[np.asarray(nan_rows, np.int64)] = True

        sizes = [int(np.prod(l.shape, dtype=np.int64))
                 for l in jax.tree_util.tree_leaves(global_params)]
        if method != "none":
            if self._ef is None:
                self._ef = self._new_ef_store()
            if self._ef.leaves and \
                    [l.shape[1] for l in self._ef.leaves] != sizes:
                raise ValueError(
                    "error-feedback store leaf sizes "
                    f"{[l.shape[1] for l in self._ef.leaves]} do not match "
                    f"the update structure {sizes}; one executor serves one "
                    f"model")
            rows = self._ef.ensure(
                [c.client_id for c in clients],
                lambda cid: [np.zeros((s,), np.float32) for s in sizes])
            ef_leaves = tuple(self._ef.leaves)
            # out-of-bounds sentinel: padded clients gather 0 residual
            # (mode="fill") and their scatter rows are dropped
            ef_rows = np.full((Nb,), self._ef.alloc, np.int32)
            ef_rows[:N] = rows
        else:
            ef_leaves, ef_rows = (), np.zeros((Nb,), np.int32)

        program = make_round_program(
            self.model, optimizer, S,
            use_prox=bool((vec.mu > 0).any()),
            use_clip=bool((vec.max_norm > 0).any()),
            method=method, stc_sparsity=float(stc_sparsity),
            use_faults=use_faults, max_update_norm=float(max_update_norm),
            topology=topology, fanout=int(fanout), use_kernel=use_kernel,
            server_lr=float(server_lr),
            interpret=interpret, mesh=self.mesh)
        if self.mesh is not None:
            # the program returns params replicated over the mesh; place the
            # first round's params the same way, or round 2 would retrace on
            # the changed input sharding (a no-op once they are placed)
            from jax.sharding import NamedSharding, PartitionSpec as P
            global_params = jax.device_put(global_params,
                                           NamedSharding(self.mesh, P()))

        t0 = time.perf_counter()
        with warnings.catch_warnings():
            # CPU backends may decline the donation; that is fine.
            warnings.filterwarnings("ignore", message=".*donated.*")
            new_global, loss, acc, ok, nnz, new_ef = program(
                global_params, xd, yd, jnp.asarray(idx),
                jnp.asarray(n_steps),
                jax.tree_util.tree_map(jnp.asarray, vec),
                jnp.asarray(w), jnp.asarray(m), jnp.asarray(nanm),
                ef_leaves, jnp.asarray(ef_rows))
        _note_dispatch()
        if method != "none":
            self._ef.leaves = list(new_ef)

        st: Dict[str, Any] = {
            "n_steps": n_steps,
            "num_samples": num_samples,
            "compression": method,
            "comp_sizes": sizes,
        }
        # reconstruct the per-leaf nnz layout per_client_payload_bytes
        # expects: one entry per leaf, None for non-STC leaves
        from repro.core.compression import DENSE_MIN_ELEMS

        def nnz_layout(per_stc_leaf):
            it = iter(per_stc_leaf)
            return [next(it) if method == "stc" and s >= DENSE_MIN_ELEMS
                    else None for s in sizes]

        def fetch():
            # the round's ONE batched device->host transfer
            l_h, a_h, ok_h, nnz_h = jax.device_get((loss, acc, ok, nnz))  # flcheck: ignore[FLC101]  -- the fused round's single batched fetch
            _note_host_sync()
            st["loss"], st["acc"] = np.asarray(l_h), np.asarray(a_h)
            if use_faults:
                st["guard_ok"] = np.asarray(ok_h)
            st["nnz"] = nnz_layout([np.asarray(a) for a in nnz_h])
            st.pop("_fetch", None)

        if sync:
            fetch()
            # timing boundary: the fetch above blocked on the whole round
            st["wall"] = time.perf_counter() - t0
            return st, new_global, None
        st["wall"] = time.perf_counter() - t0   # submission time
        st["_fetch"] = fetch
        return st, new_global, fetch

    # ------------------------------------------------------------------
    def run_cohort(self, clients: Sequence, global_params: PyTree,
                   round_id: int) -> List[Dict[str, Any]]:
        """Train ``clients`` as one jitted program; per-client results.

        Args:
            clients: cohort of :class:`repro.core.client.Client`s (uniform
                batch size and optimizer *family*; every per-client
                optimizer hyperparameter, FedProx mu and grad-clip norm
                are vectorized — mixed families raise ``ValueError``
                naming the clients).
            global_params: the global model pytree every client starts
                from.
            round_id: seeds each client's epoch/batch shuffle exactly like
                the sequential path (the async engine passes its wave id).

        Returns:
            One ``Client.train``-shaped dict per client (``update``,
            ``num_samples``, ``metrics``, ``train_time``), in cohort
            order — ready for the compression/encryption/upload stages.
        """
        if not clients:
            return []
        st = self.run_cohort_stacked(clients, global_params, round_id)
        return self.per_client_results(clients, st)

    # ------------------------------------------------------------------
    # In-program compression (error feedback on device, per client id)
    # ------------------------------------------------------------------
    def _ef_gather(self, clients: Sequence, leaves: List[Any]) -> List[Any]:
        """Fetch the cohort's error-feedback residual rows, one
        (N, leaf_size) f32 matrix per update leaf, from the tiered store.
        Rows are keyed by client id: hot rows gather straight off the
        device, spilled rows reload from their pinned host copies
        bit-identically, never-seen clients start from zero — so async
        waves and million-client populations hit the same residual
        semantics as the original device-only store.  Under the client
        mesh the hot tier stays sharded along its row axis, so the
        round-trip gather/scatter never funnels residuals through one
        device."""
        sizes = [int(np.prod(l.shape[1:], dtype=np.int64)) for l in leaves]
        if self._ef is None:
            self._ef = self._new_ef_store()
        if self._ef.leaves and \
                [l.shape[1] for l in self._ef.leaves] != sizes:
            raise ValueError(
                "error-feedback store leaf sizes "
                f"{[l.shape[1] for l in self._ef.leaves]} do not match the "
                f"update structure {sizes}; one executor serves one model")
        ids = [c.client_id for c in clients]
        res = self._ef.gather(
            ids, lambda cid: [np.zeros((s,), np.float32) for s in sizes])
        return res, ids

    # ------------------------------------------------------------------
    def ef_state(self) -> Dict[str, Any]:
        """Serializable snapshot of the error-feedback residual store
        (checkpointing — ``Trainer.save_checkpoint``).  Per-client host
        np copies drawn from BOTH tiers (hot device rows leave in one
        batched fetch; spilled rows are already host-resident), so a
        kill/resume boundary reproduces every residual bit-identically
        regardless of which tier held it."""
        if self._ef is None:
            return {"format": 2, "clients": {}}
        state = self._ef.state()
        state["format"] = 2
        return state

    def load_ef_state(self, state: Dict[str, Any]) -> None:
        """Restore :meth:`ef_state` into the warm tier (rows re-heat — and
        re-shard onto the client mesh — on their next gather).  Accepts
        the legacy dense ``{"rows", "store"}`` snapshot format too."""
        self._ef = self._new_ef_store()
        if "clients" in state:
            self._ef.load_state(state)
            return
        rows = {str(k): int(v)  # flcheck: ignore[FLC102]  -- checkpoint dict holds host ints
                for k, v in state.get("rows", {}).items()}
        store = [np.asarray(m, np.float32) for m in state.get("store", [])]
        self._ef.load_state(
            {"clients": {cid: [m[r] for m in store]
                         for cid, r in rows.items()}})

    # ------------------------------------------------------------------
    def compress_stacked(self, st: Dict[str, Any], clients: Sequence,
                         method: str, stc_sparsity: float = 0.01,
                         interpret: Optional[bool] = None) -> Dict[str, Any]:
        """In-program update compression with error feedback.

        Replaces ``st["updates"]`` with the *sent* (compressed then
        dense-decoded) values — exactly what the sequential
        ``Client.compression`` stage produces via
        ``compression.compress_with_feedback``, but vectorized over the
        stacked cohort and never leaving the device(s):

        * each stacked leaf (N_bucket, *shape) is flattened to
          (N_bucket, size) and, error-corrected by the client's stored
          residual, run through the batched Pallas kernel
          (``kernels.stc_topk.stc_compress_batched`` /
          ``kernels.quant.int8_roundtrip_batched``) — per shard of the
          client mesh when distributed;
        * leaves smaller than 64 elements stay dense (matching the
          sequential stage) and reset their residual;
        * the new residual (corrected - sent) is scattered back into the
          per-client-id store, so round-over-round semantics match
          ``Client._residual`` — including across async dispatch waves;
        * per-client STC non-zero counts ride along in ``st["nnz"]`` (one
          (N_bucket,) device vector per compressed leaf) for wire-size
          accounting via :meth:`per_client_payload_bytes` — no per-leaf
          host syncs, no gathered updates.
        """
        if method not in ("stc", "int8"):
            raise ValueError(
                f"unknown in-program compression {method!r}; expected "
                f"'stc' or 'int8'")
        from repro.core.compression import DENSE_MIN_ELEMS
        from repro.kernels import ops as kops

        leaves, treedef = jax.tree_util.tree_flatten(st["updates"])
        nb = leaves[0].shape[0]
        n = len(clients)
        residuals, ids = self._ef_gather(clients, leaves)
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P(CLIENT_AXIS, None))
        sent_leaves, new_res, nnz_list, sizes = [], [], [], []
        for leaf, res in zip(leaves, residuals):
            size = int(np.prod(leaf.shape[1:], dtype=np.int64))
            sizes.append(size)
            flat = leaf.reshape(nb, size).astype(jnp.float32)
            resb = jnp.pad(res, ((0, nb - n), (0, 0)))
            if sharding is not None:
                resb = jax.device_put(resb, sharding)
            corrected = flat + resb
            if size < DENSE_MIN_ELEMS:    # tiny tensors stay dense
                sent, nnz = corrected, None
            elif method == "stc":
                sent, nnz = kops.stc_compress_batched(
                    corrected, stc_sparsity, interpret=interpret,
                    mesh=self.mesh)
            else:
                sent, _ = kops.int8_roundtrip_batched(
                    corrected, interpret=interpret, mesh=self.mesh)
                nnz = None
            new_res.append((corrected - sent)[:n])
            sent_leaves.append(sent.reshape(leaf.shape))
            nnz_list.append(nnz)
        self._ef.scatter(ids, new_res)
        _note_dispatch()               # the staged compression stage
        out = dict(st)
        out["updates"] = jax.tree_util.tree_unflatten(treedef, sent_leaves)
        out["nnz"] = nnz_list
        out["comp_sizes"] = sizes
        out["compression"] = method
        return out

    # ------------------------------------------------------------------
    @staticmethod
    def per_client_payload_bytes(st: Dict[str, Any]) -> List[int]:
        """Wire sizes of a compressed stacked round, one host sync total.

        Mirrors ``compression.payload_bytes`` leaf-for-leaf: STC leaves
        from the in-program per-client nnz counts (all fetched in one
        ``jax.device_get``), int8 leaves 1 byte/element + scale, tiny
        dense leaves (size < ``compression.DENSE_MIN_ELEMS``) raw f32
        bytes."""
        from repro.core.compression import DENSE_MIN_ELEMS

        method = st["compression"]
        n = len(st["num_samples"])
        base = 0
        for size, nnz in zip(st["comp_sizes"], st["nnz"]):
            if size < DENSE_MIN_ELEMS:
                base += size * 4                      # dense f32 leaf
            elif method == "int8":
                base += size + 4                      # int8 + scale
        totals = np.full((n,), base, np.int64)
        stc_nnz = [a for a in st["nnz"] if a is not None]
        if stc_nnz:
            # the documented single transfer of the compressed round: all
            # per-leaf nnz counts fetched at once for wire accounting
            if any(not isinstance(a, np.ndarray) for a in stc_nnz):
                _note_host_sync()      # fused rounds pass pre-fetched np
            for counts in jax.device_get(stc_nnz):  # flcheck: ignore[FLC101]  -- one batched nnz fetch
                counts = counts[:n].astype(np.int64)
                # vectorized compression.stc_leaf_bytes
                totals += counts * 4 + (counts + 7) // 8 + 4
        return totals.tolist()

    # ------------------------------------------------------------------
    def aggregate_stacked(self, st: Dict[str, Any],
                          interpret: Optional[bool] = None,
                          use_kernel: bool = False,
                          mask: Optional[np.ndarray] = None,
                          guard: bool = False,
                          max_update_norm: float = 0.0,
                          topology: str = "flat",
                          fanout: int = 0) -> PyTree:
        """FedAvg delta from stacked updates without per-client gathering.

        Flattens the stacked update pytree to (N_bucket, D) and reduces it
        in place: under the client mesh, per-shard partial weighted sums
        with the ``psum``-epilogue kernel (client dim stays sharded); on a
        single device, one stacked einsum (or the chunked streaming Pallas
        kernel with ``use_kernel``) over the already-stacked matrix — no
        per-client slicing either way.  Compressed (``compress_stacked``)
        and dense stacked updates flow through identically: compression
        happens upstream of the weighted sum, and staleness/weight folding
        is untouched.  Returns the weighted-average (f32) delta as a
        pytree shaped like the global params (the updates mirror their
        structure).

        Fault tolerance (``cfg.faults`` — see docs/faults.md): ``mask``
        zero-weights failed / deadline-exceeded clients ((N,) 0/1 host
        array), ``guard`` adds the on-device NaN/Inf row check on the
        stacked matrix (plus a global-L2 ``max_update_norm`` outlier bound
        when > 0), and the surviving weights renormalize to sum 1 — the
        survivors-only FedAvg.  Guarded rows are zeroed in the data before
        the weighted sum (0-weighting alone would still propagate NaN) and
        the per-client verdict lands in ``st["guard_ok"]`` (device (N_b,)
        bool) for fault accounting.  All of this is skipped — the weight
        vector and program are byte-identical to a fault-free build — when
        ``mask``/``guard`` are left at their defaults.

        ``topology="hierarchical"`` reduces through the edge→region→global
        tree (``fedavg_aggregate_tree``; per-shard tree + ``psum`` top
        tier under the mesh) with ``fanout`` children per node; every
        tier is linear in the weight vector, so staleness folding, fault
        masking and compressed updates compose unchanged, and
        ``fanout >= cohort`` reproduces the flat result bit-for-bit."""
        from repro.core.aggregation import fedavg_weights
        from repro.kernels import ops as kops
        from repro.kernels.fedavg_agg import (fedavg_aggregate_sharded,
                                              fedavg_aggregate_tree)

        leaves, treedef = jax.tree_util.tree_flatten(st["updates"])
        nb = leaves[0].shape[0]
        num_samples = st["num_samples"]
        w = np.zeros((nb,), np.float32)
        w[: len(num_samples)] = fedavg_weights(num_samples)
        flat = jnp.concatenate([l.reshape(nb, -1) for l in leaves], axis=1)
        if mask is not None or guard:
            wj = jnp.asarray(w)
            if mask is not None:
                m = np.zeros((nb,), np.float32)
                m[: len(mask)] = np.asarray(mask, np.float32)
                wj = wj * jnp.asarray(m)
            if guard:
                ok = jnp.isfinite(flat).all(axis=1)
                if max_update_norm > 0:
                    norms = jnp.sqrt(jnp.sum(
                        jnp.square(flat.astype(jnp.float32)), axis=1))
                    # non-finite norms compare False, so the & is redundant
                    # only for finite rows — keep both checks explicit
                    ok = ok & (norms <= max_update_norm)
                wj = wj * ok.astype(jnp.float32)
                # zero rejected rows in the DATA too: 0 * NaN is NaN, so a
                # zero weight alone cannot neutralize a poisoned update
                flat = jnp.where(ok[:, None], flat, 0.0)
                st["guard_ok"] = ok
            wsum = jnp.sum(wj)
            # survivors-only FedAvg; all-failed rounds yield a zero delta
            # (params unchanged) instead of a 0/0 NaN
            wj = jnp.where(wsum > 0, wj / wsum, 0.0)
            w = wj
        tree = topology == "hierarchical"
        if self.mesh is not None:
            delta = fedavg_aggregate_sharded(
                flat, jnp.asarray(w), self.mesh,
                interpret=interpret,
                fanout=(fanout or int(np.ceil(np.sqrt(nb)))) if tree else 0)
        elif tree:
            delta = fedavg_aggregate_tree(
                flat, jnp.asarray(w), fanout=fanout, use_kernel=use_kernel,
                interpret=interpret)
        elif use_kernel:
            delta = kops.fedavg_aggregate(flat, jnp.asarray(w),
                                          interpret=interpret)
        else:
            delta = jnp.einsum("n,nd->d", jnp.asarray(w),
                               flat.astype(jnp.float32), precision=HIGHEST)
        _note_dispatch()               # the staged aggregation stage
        # unravel by leaf shape (slices are views; no copy of the model)
        out, off = [], 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape[1:], dtype=np.int64))
            out.append(delta[off: off + size].reshape(leaf.shape[1:]))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------------
    @staticmethod
    def per_client_results(clients: Sequence, st: Dict[str, Any],
                           include_update: bool = True
                           ) -> List[Dict[str, Any]]:
        """Slice stacked results into ``Client.train``-shaped dicts.

        ``include_update=True`` gathers each client's update to the default
        device (the non-distributed/compression-compatible path);
        ``include_update=False`` keeps the stacked updates on the mesh —
        the distributed fast path aggregates them separately and only
        needs the metrics/virtual-clock fields here."""
        updates, loss, acc = st["updates"], st["loss"], st["acc"]
        n_steps, wall = st["n_steps"], st["wall"]
        # Shared wall time -> per-client base times by step share (the
        # virtual clock's per-step-cost model; see module docstring).
        total_steps = max(int(n_steps.sum()), 1)
        # loss/acc/n_steps are host np arrays (fetched once by
        # run_cohort_stacked); tolist() converts to Python scalars in bulk
        loss, acc = loss.tolist(), acc.tolist()
        steps_f = n_steps.astype(np.float64).tolist()
        results = []
        for i, c in enumerate(clients):
            res = {
                "num_samples": len(c.data),
                "metrics": {"loss": loss[i],
                            "accuracy": acc[i],
                            "batches": steps_f[i]},
                "train_time": wall * steps_f[i] / total_steps,
            }
            if include_update:
                res["update"] = jax.tree_util.tree_map(
                    lambda a, i=i: a[i], updates)
            results.append(res)
        return results
