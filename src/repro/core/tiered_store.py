"""Tiered per-client row storage: bounded device LRU tier + host backing.

Every per-client array the batched engine keeps — the data pool's padded
x/y rows, the error-feedback residual matrices — used to be device-resident
and O(touched population).  A million-client federation touches ~cohort
clients per round, so the working set is tiny; this module bounds the
device tier and makes everything else cost host bytes (or nothing at all):

* **hot tier** — per-leaf ``(alloc, *shape)`` device arrays holding up to
  ``capacity`` client rows, managed LRU.  Cohort assembly gathers only the
  selected rows; inserting/evicting touches one batched scatter/fetch per
  leaf, never a per-client device call.
* **warm tier** (``spill="host"``) — rows evicted from the device tier are
  fetched once (one batched transfer per leaf) into pinned host numpy
  copies and reloaded bit-identically on the next gather.  This is the
  error-feedback residual path: residuals are *state* and must survive
  eviction exactly (including through checkpoint/resume —
  :meth:`TieredRowStore.state` round-trips both tiers).
* **recompute** (``spill="drop"``) — evicted rows are discarded because the
  owner can rebuild them from its source of truth (the data pool re-pads
  from ``client.data``; virtual datasets regenerate ``client.data`` itself
  from the seed).  Cold clients cost zero storage in any tier.

The device tier never evicts a row that the *current* cohort pins, so a
cohort larger than ``capacity`` transparently grows the tier to the cohort
size for that round (the documented device-memory bound is
``max(capacity, cohort)`` rows).  Row slots are recycled through a free
list.  The hot tier is allocated once, at ``capacity`` rows, on the first
insert, so its shape stays fixed while the resident rows fit: the fused
round program takes the error-feedback store's matrices whole, and each
new shape would be a recompile.  Callers size ``capacity`` to the clients
a run can touch.

See ``docs/scale.md`` for the end-to-end walkthrough.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Sequence

import jax.numpy as jnp
import numpy as np


def set_rows(matrix, rows, values):
    """``matrix.at[rows].set(values, mode="drop")`` as a gather and a select.

    Row ``r`` of the result is ``values[i]`` where ``rows[i] == r``, else
    the old row; out-of-range entries of ``rows`` write nothing, and no
    in-range entry may repeat.  XLA's partitioned scatter into a matrix
    sharded along its rows wrote wrong rows on a TPU v5e 2x2 (JAX 0.9.0),
    while the partitioned row gather is exact, so row writes go this way.
    """
    rows = jnp.asarray(rows)
    match = rows[None, :] == jnp.arange(matrix.shape[0],
                                        dtype=rows.dtype)[:, None]
    picked = jnp.take(jnp.asarray(values, matrix.dtype),
                      jnp.argmax(match, axis=1), axis=0)
    hit = jnp.any(match, axis=1).reshape((-1,) + (1,) * (matrix.ndim - 1))
    return jnp.where(hit, picked, matrix)


class TieredRowStore:
    """Bounded device-resident LRU cache of per-client rows over host spill.

    Args:
        capacity: device-tier bound (rows); cohorts larger than this pin
            the tier open for the round (see module docstring).
        spill: ``"host"`` keeps evicted rows as pinned host numpy copies
            (reloaded bit-identically); ``"drop"`` discards them — the
            caller's ``make_row`` recomputes on the next appearance.
        mesh: optional 1-D client mesh; device leaves are sharded along
            the row axis and allocation stays a multiple of ``mesh.size``.
        name: label for error messages.
    """

    def __init__(self, capacity: int, spill: str = "host", mesh=None,
                 name: str = "store"):
        if spill not in ("host", "drop"):
            raise ValueError(f"unknown spill policy {spill!r}; "
                             f"expected 'host' or 'drop'")
        if capacity < 1:
            raise ValueError(f"{name}: capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.spill = spill
        self.mesh = mesh
        self.name = name
        self.leaves: List[Any] = []            # device (alloc, *shape)
        self.rows: Dict[str, int] = {}         # id -> hot-tier row
        self._lru: "OrderedDict[str, None]" = OrderedDict()
        self._free: List[int] = []
        self._host: Dict[str, List[np.ndarray]] = {}   # spilled rows
        self.stats = {"inserts": 0, "evictions": 0, "spills": 0,
                      "reloads": 0, "recomputes": 0}

    # ------------------------------------------------------------------
    def __contains__(self, cid: str) -> bool:
        return cid in self.rows or cid in self._host

    def __len__(self) -> int:
        return len(self.rows) + len(self._host)

    @property
    def alloc(self) -> int:
        return self.leaves[0].shape[0] if self.leaves else 0

    def spilled_ids(self):
        return self._host.keys()

    def device_bytes(self) -> int:
        """Bytes held by the hot tier (the flat-vs-population gate)."""
        return sum(int(leaf.nbytes) for leaf in self.leaves)

    def host_bytes(self) -> int:
        return sum(int(r.nbytes) for rows in self._host.values()
                   for r in rows)

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Drop every tier (dataset swapped / shapes changed)."""
        self.leaves = []
        self.rows = {}
        self._lru = OrderedDict()
        self._free = []
        self._host = {}

    # ------------------------------------------------------------------
    def _place(self, leaves: List[Any]) -> List[Any]:
        if self.mesh is None or not leaves:
            return leaves
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P("clients",
                                        *(None,) * (leaves[0].ndim - 1)))
        return [jax.device_put(m, sh) for m in leaves]

    def _grow(self, cap_eff: int) -> None:
        """Grow the hot-tier allocation to ``cap_eff`` rows."""
        new_alloc = cap_eff
        if self.mesh is not None:
            m = self.mesh.size
            new_alloc = -(-new_alloc // m) * m
        old = self.alloc
        if new_alloc <= old:
            return
        self.leaves = self._place([
            jnp.pad(leaf, ((0, new_alloc - old),)
                    + ((0, 0),) * (leaf.ndim - 1))
            for leaf in self.leaves])
        self._free.extend(range(old, new_alloc))

    def _evict(self, count: int, pinned: set) -> None:
        """Evict ``count`` least-recently-used rows not pinned this round.

        All evicted rows of one call leave the device in ONE batched fetch
        per leaf (host spill) or are simply forgotten (drop/recompute)."""
        victims = []
        for cid in self._lru:
            if cid not in pinned:
                victims.append(cid)
                if len(victims) == count:
                    break
        if len(victims) < count:
            raise RuntimeError(
                f"{self.name}: cannot evict {count} rows — "
                f"{len(self._lru)} resident, {len(pinned)} pinned")
        if self.spill == "host":
            idx = np.asarray([self.rows[c] for c in victims])
            # one batched device->host fetch per leaf for the whole batch
            fetched = [np.asarray(leaf[idx]) for leaf in self.leaves]
            for i, cid in enumerate(victims):
                self._host[cid] = [np.array(f[i]) for f in fetched]
            self.stats["spills"] += len(victims)
        for cid in victims:
            self._free.append(self.rows.pop(cid))
            self._lru.pop(cid)
        self.stats["evictions"] += len(victims)

    # ------------------------------------------------------------------
    def ensure(self, ids: Sequence[str],
               make_row: Callable[[str], List[np.ndarray]]) -> np.ndarray:
        """Make every id hot-tier resident; return their row indices.

        Missing ids are filled from the warm tier (bit-identical reload)
        when spilled, else from ``make_row(cid)`` — a list of per-leaf row
        values (the recompute / first-upload path).  Evicts LRU rows as
        needed; ids in ``ids`` are pinned and never evicted by this call.
        All inserts land in one batched scatter per leaf.
        """
        ids = list(ids)
        pinned = set(ids)
        missing = [c for c in ids if c not in self.rows]
        if missing:
            cap_eff = max(self.capacity, len(pinned))
            values: List[List[np.ndarray]] = []
            for cid in missing:
                if cid in self._host:
                    values.append(self._host.pop(cid))
                    self.stats["reloads"] += 1
                else:
                    values.append([np.asarray(v) for v in make_row(cid)])
                    self.stats["recomputes"] += 1
            if not self.leaves:
                self.leaves = self._place([
                    jnp.zeros((0,) + v.shape, v.dtype) for v in values[0]])
            # keep resident <= cap_eff: evict LRU first (cap_eff >= the
            # pinned count, so enough unpinned victims always exist),
            # then grow the allocation toward the bound if still short
            over = len(self.rows) + len(missing) - cap_eff
            if over > 0:
                self._evict(over, pinned)
            if len(missing) > len(self._free):
                self._grow(cap_eff)
            slots = [self._free.pop() for _ in missing]
            stacked = [np.stack([v[li] for v in values])
                       for li in range(len(self.leaves))]
            sl = np.asarray(slots, np.int32)
            self.leaves = self._place([
                set_rows(leaf, sl, vals)
                for leaf, vals in zip(self.leaves, stacked)])
            for cid, slot in zip(missing, slots):
                self.rows[cid] = slot
            self.stats["inserts"] += len(missing)
        for cid in ids:                # refresh recency, newest last
            self._lru.pop(cid, None)
            self._lru[cid] = None
        return np.asarray([self.rows[c] for c in ids], np.int32)

    # ------------------------------------------------------------------
    def gather(self, ids: Sequence[str],
               make_row: Callable[[str], List[np.ndarray]]) -> List[Any]:
        """Device-side row gather of ``ids`` (ensuring residency first).

        Returns one ``(len(ids), *shape)`` device array per leaf."""
        rows = self.ensure(ids, make_row)
        idx = jnp.asarray(rows)
        return [jnp.take(leaf, idx, axis=0) for leaf in self.leaves]

    def scatter(self, ids: Sequence[str], leaves: List[Any]) -> None:
        """Write per-leaf ``(len(ids), *shape)`` values back to hot rows.

        Ids must be resident (callers scatter right after a gather)."""
        idx = np.asarray([self.rows[c] for c in ids], np.int32)
        self.leaves = self._place([
            set_rows(m, idx, vals) for m, vals in zip(self.leaves, leaves)])

    # ------------------------------------------------------------------
    def drop(self, cid: str) -> None:
        """Forget one client's rows in every tier (data invalidation)."""
        if cid in self.rows:
            self._free.append(self.rows.pop(cid))
            self._lru.pop(cid, None)
        self._host.pop(cid, None)

    def pad_dim1(self, new_size: int) -> None:
        """Grow every leaf's axis-1 (the sample dim of pooled data rows).

        Zero-pads device leaves and any spilled host rows alike, so
        growing the federation's max sample count stays a metadata-level
        operation instead of a re-upload."""
        if not self.leaves:
            return
        self.leaves = self._place([
            jnp.pad(leaf, ((0, 0), (0, new_size - leaf.shape[1]))
                    + ((0, 0),) * (leaf.ndim - 2))
            for leaf in self.leaves])
        for cid, rows in self._host.items():
            self._host[cid] = [
                np.pad(r, ((0, new_size - r.shape[0]),)
                       + ((0, 0),) * (r.ndim - 1)) for r in rows]

    # ------------------------------------------------------------------
    def state(self) -> Dict[str, Any]:
        """Checkpoint snapshot: every client's rows from BOTH tiers.

        Hot rows leave the device in one batched fetch per leaf; spilled
        rows are already host-resident.  The snapshot is tier-agnostic —
        restoring onto a differently-sized device tier reproduces the
        same values bit-identically (rows land in the warm tier and
        reload on demand)."""
        out: Dict[str, List[np.ndarray]] = {}
        if self.rows:
            cids = list(self.rows)
            idx = np.asarray([self.rows[c] for c in cids])
            fetched = [np.asarray(leaf[idx]) for leaf in self.leaves]
            for i, cid in enumerate(cids):
                out[cid] = [np.array(f[i]) for f in fetched]
        for cid, rows in self._host.items():
            out[cid] = [np.array(r) for r in rows]
        return {"clients": out}

    def load_state(self, state: Dict[str, Any]) -> None:
        """Restore :meth:`state` into the warm tier (lazy re-heating)."""
        self.reset()
        for cid, rows in state.get("clients", {}).items():
            self._host[str(cid)] = [np.asarray(r) for r in rows]
