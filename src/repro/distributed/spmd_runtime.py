"""Device-parallel SPMD execution of the sharded runtime's rank views.

Since PR 3 the ``ShardedRuntime`` models p ranks — per-rank caches, a
rank-indexed ``fetch_rows`` transport, an all-to-all ``serve_rows``
matrix — but the rank views *execute* as a sequential Python loop over p
in-process engines. This module runs them as real SPMD compute over a
JAX device mesh, the way the static epoch ``async_engine`` already does
— and, since PR 8, it does so *asynchronously*:

- **Resident rank-sharded state** — the padded row buffer ``[p, H, W]``
  persists on device across execution units. Each unit only *patches*
  the rows that are new or drifted (the idiom ``ResidencyManager`` uses
  for the device tier): reused rows cost zero H2D traffic and are
  reported as ``upload_bytes_saved``. Freshness is an invalidation
  contract — the runtime's coherence fanout (and the streaming engine's
  mid-batch delete notification) drop mutated ids from the buffer, so a
  mapped id always matches ``store.row(v)`` at pack time.
- **Width-bucketed collective transport** — the control plane
  (``fetch_rows`` cache admission, stats, the modeled ``serve_rows``
  matrix) stays host-side and untouched; its recorded ``"miss"`` events
  become serve lists, bucketed onto a fixed geometric ladder of pow-2
  width rungs (``_PAIR_WIDTH_LADDER``) with windowed high-water
  capacities, so skewed batches stop shipping max-width padding *and*
  the compiled collective keeps a canonical shape across units. One
  ``jax.lax.all_to_all`` per rung moves exactly those rows owner ->
  requester; the measured ``CollectiveLedger`` reconciles
  *by construction* against the modeled matrix, and the recovered
  padding shows up as ``bytes_on_wire`` vs ``bytes_on_wire_single``
  (what the old single-width scheme would have moved).
- **Hub-fragment fan-out** — under a hub-aware partition
  (``core.partition.HubPartition``) a fetched split-hub row does not
  ship whole from its owner: every rank serves its *fragment* (slot
  keyed ``n + 1 + v`` so fragment and full-row residency never
  collide), the requester's own fragment stays local, and each pair
  touching the row expands into sub-pairs whose counts are summed by
  an additive scatter — the deterministic fragment reduction.
  Fragments are disjoint contiguous slices of the sorted row, so the
  reduction is exact and the measured ledger still reconciles
  row-for-row against the runtime's fragment-charged serve matrix.
- **Double-buffered units** — ``dispatch()`` packs, patches, and
  launches a unit without blocking; ``PendingUnit.wait()`` is the only
  reconciliation barrier (``jax.block_until_ready``). Callers overlap
  the pack + collective of unit k+1 with the in-flight intersect of
  unit k; because the ledger is computed host-side at dispatch, the
  measured-vs-modeled assertion still holds row-for-row before the
  device work ever completes. ``run()`` is dispatch + wait, the
  unpipelined shape consumers used before.
- **On-device intersect** — every rank gathers its pair worklist from
  the combined [resident | fetched] buffer; pairs are bucketed by their
  pow-2 width class and counted per bucket with the Pallas
  ``intersect_count`` kernel when ``use_kernel`` (the same kernel
  ``delta_intersect``/``point_query`` dispatch to), else the vectorized
  ``count_bsearch_jnp`` path. Counts are exact integers either way, so
  SPMD execution — pipelined or not — is bit-exact against the
  loop-mode engines; the property tests compare them field-for-field.

Consumers: ``serving.engine.ShardedQueryEngine(execution="spmd")`` and
``streaming.incremental.StreamingLCCEngine(execution="spmd")``; drivers
``launch/query_serve.py --spmd [--pipeline]`` and
``launch/stream_run.py --spmd [--pipeline]``. Multi-device CPU runs
force host devices via ``ensure_host_devices``
(``--xla_force_host_platform_device_count``), preserving any
user-provided ``XLA_FLAGS``. See docs/spmd.md for the resident-buffer
patch protocol and where the reconciliation barriers sit.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.intersect import count_bsearch_jnp
from ..kernels.bucketing import pow2_ceil
from ..kernels.intersect_count import intersect_count
from ..kernels.ops import default_interpret
from ..obs import trace as obs_trace

__all__ = [
    "CollectiveLedger",
    "PendingUnit",
    "ShardWork",
    "SpmdIntersectExecutor",
    "ensure_host_devices",
]

ID_BYTES = 4
_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count"
# bounded bucket counts — serve buckets each cost one all_to_all
# launch (adaptive smallest-merge split, exact wire accounting); pair
# buckets one kernel call each, on the fixed geometric width ladder
# below (clipped to the buffer width) so the compiled intersect shapes
# stay canonical across units.
_PAIR_WIDTH_LADDER = (16, 64, 256, 1 << 30)
# Windowed high-water capacities: per-rung counts follow the max need
# over the last _CAP_WINDOW units, so capacities (and the compiled
# programs keyed on them) stay put through per-unit jitter, grow
# immediately on demand, and decay once a peak ages out of the window.
_CAP_WINDOW = 16


def ensure_host_devices(n: int, *, strict: bool = True) -> int:
    """Make at least ``n`` JAX devices available: host devices on the
    CPU, real chips on an accelerator.

    On the CPU, appends ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS`` — *preserving* any flags already set by the user or
    CI, and never overriding an existing device-count directive (jax
    pins the device count at first backend init, so an explicit
    external value must win). An existing directive's *value* is parsed
    and compared against ``n``: a smaller pinned count fails here,
    immediately and by name, instead of surfacing later as a confusing
    generic device shortage. The flag shapes only the CPU backend, so
    when JAX comes up on an accelerator the environment is restored and
    the chips themselves are counted. Returns the device count actually
    available; with ``strict`` raises if it is still smaller than ``n``
    (e.g. jax was already initialized single-device before this call,
    or the host has fewer chips). This is the one home of the
    flag-preserving logic — drivers, benchmarks, and subprocess test
    scripts call it instead of hand-editing ``XLA_FLAGS``."""
    n = int(n)
    had_flags = "XLA_FLAGS" in os.environ
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_DEVCOUNT_FLAG) + r"\s*=\s*(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_DEVCOUNT_FLAG}={n}".strip()
    devs = jax.devices()  # first call initializes with the flags
    have = len(devs)
    platform = devs[0].platform
    if platform != "cpu":
        if had_flags:
            os.environ["XLA_FLAGS"] = flags
        else:
            os.environ.pop("XLA_FLAGS", None)
        if strict and have < n:
            raise RuntimeError(
                f"need {n} devices for SPMD execution but this {platform} "
                f"host has {have} ({devs[0].device_kind})"
            )
        return have
    if strict and have < n:
        if m is not None and int(m.group(1)) < n:
            raise RuntimeError(
                f"XLA_FLAGS already pins {_DEVCOUNT_FLAG}={m.group(1)}, "
                f"smaller than the {n} devices SPMD execution needs — "
                f"raise it to at least {n} (or unset it and let "
                "ensure_host_devices set the count)"
            )
        raise RuntimeError(
            f"need {n} devices for SPMD execution but only {have} are "
            f"available; set XLA_FLAGS={_DEVCOUNT_FLAG}={n} before the "
            "first jax use (jax locks the device count at first init)"
        )
    return have


@dataclasses.dataclass
class ShardWork:
    """One rank's slice of an execution unit.

    ``rows_held`` maps vertex id -> sorted 1-D row for every row that is
    rank-resident this unit (local shard rows, cache-hit payloads,
    device-tier mirror rows) — content is whatever the loop-mode engine
    would have read, so staleness semantics carry over unchanged.
    ``fetched_ids`` are the remote misses (in fetch order): their content
    is *not* taken from this rank — it ships from the owner's buffer
    through the collective. Every id referenced by ``pair_a``/``pair_b``
    must be in exactly one of the two."""

    rank: int
    pair_a: np.ndarray  # int64 [E] vertex ids
    pair_b: np.ndarray  # int64 [E]
    rows_held: Dict[int, np.ndarray]
    fetched_ids: Sequence[int] = ()


@dataclasses.dataclass
class CollectiveLedger:
    """Measured collective + upload traffic of SPMD execution units.

    ``rows_shipped[owner, requester]`` counts rows that travelled
    through ``all_to_all`` — the measured analogue of the runtime's
    modeled ``serve_rows`` matrix (the executor asserts they agree
    delta-for-delta). ``bytes_payload`` is the true row payload moved
    (sum of shipped row widths, the quantity the ``NetworkModel``
    charges); ``bytes_on_wire`` is what the width-bucketed collectives
    actually moved between devices (excludes the self-chunk), and
    ``bytes_on_wire_single`` is what the pre-bucketing single-max-width
    collective *would* have moved — their difference is the recovered
    padding. ``bytes_uploaded`` / ``upload_bytes_saved`` split each
    unit's working set into rows that had to be H2D-patched into the
    resident buffer vs rows already resident from earlier units (a full
    re-pack would upload the sum of both). Wall-clock fields:
    ``device_wall_s`` is dispatch-to-ready per unit; ``overlap_wait_s``
    is the part actually spent blocked in ``wait()`` — under pipelining
    the gap between them is compute the overlap hid."""

    p: int
    rows_shipped: np.ndarray  # [p, p] int64, owner -> requester
    bytes_payload: int = 0
    bytes_on_wire: int = 0
    bytes_on_wire_single: int = 0
    bytes_uploaded: int = 0
    upload_bytes_saved: int = 0
    n_patches: int = 0
    n_collectives: int = 0
    n_pairs: int = 0
    device_wall_s: float = 0.0
    overlap_wait_s: float = 0.0

    @staticmethod
    def zero(p: int) -> "CollectiveLedger":
        return CollectiveLedger(p=p, rows_shipped=np.zeros((p, p), np.int64))

    def add(self, other: "CollectiveLedger") -> None:
        assert other.p == self.p
        self.rows_shipped += other.rows_shipped
        self.bytes_payload += other.bytes_payload
        self.bytes_on_wire += other.bytes_on_wire
        self.bytes_on_wire_single += other.bytes_on_wire_single
        self.bytes_uploaded += other.bytes_uploaded
        self.upload_bytes_saved += other.upload_bytes_saved
        self.n_patches += other.n_patches
        self.n_collectives += other.n_collectives
        self.n_pairs += other.n_pairs
        self.device_wall_s += other.device_wall_s
        self.overlap_wait_s += other.overlap_wait_s

    @property
    def total_rows(self) -> int:
        return int(self.rows_shipped.sum())

    @property
    def wire_padding_saved(self) -> int:
        """Wire bytes the width-bucketed collectives did NOT move
        compared to the single-max-width baseline."""
        return int(self.bytes_on_wire_single - self.bytes_on_wire)

    def to_dict(self) -> dict:
        return {
            "p": self.p,
            "rows_shipped": int(self.rows_shipped.sum()),
            "bytes_payload": int(self.bytes_payload),
            "bytes_on_wire": int(self.bytes_on_wire),
            "bytes_on_wire_single": int(self.bytes_on_wire_single),
            "wire_padding_saved": self.wire_padding_saved,
            "bytes_uploaded": int(self.bytes_uploaded),
            "upload_bytes_saved": int(self.upload_bytes_saved),
            "n_patches": int(self.n_patches),
            "n_collectives": int(self.n_collectives),
            "n_pairs": int(self.n_pairs),
            "device_wall_s": self.device_wall_s,
            "overlap_wait_s": self.overlap_wait_s,
        }


class _ResidentShardBuffer:
    """The persistent rank-sharded row buffer ``[p, H, W]``.

    Slot ``H-1`` of every rank is a permanent all-sentinel pad row; data
    slots hold one adjacency row each, keyed by vertex id per rank. The
    numpy ``mirror`` is authoritative; ``device`` is its sharded twin
    (``NamedSharding`` over the executor's mesh) updated by in-place
    ``.at[].set`` patches — the same epoch/patch idiom as the device
    tier's ``ResidencyManager``, minus the scoring (admission here is
    "whatever this unit needs", eviction is LRU among slots the current
    unit does not reference).

    Freshness contract: a mapped id's mirror content equals
    ``store.row(v)`` as of the last unit that wrote it. Callers MUST
    route every store mutation through ``invalidate`` before the next
    dispatch (the engines register on the runtime's coherence fanout,
    and the streaming engine notifies deletions mid-batch); ``audit``
    verifies the contract against an authoritative store."""

    def __init__(self, p: int, sentinel: int, mesh: Mesh, axis: str):
        self.p = int(p)
        self.sentinel = int(sentinel)
        self.mesh = mesh
        self.axis = axis
        self.h = 0  # slots per rank, incl. the trailing pad row
        self.w = 0
        self.mirror: Optional[np.ndarray] = None  # [p, h, w] int32
        self.device = None  # jnp twin, sharded P(axis)
        self.slot_of: List[Dict[int, int]] = [dict() for _ in range(p)]
        self.slot_ids: Optional[np.ndarray] = None  # [p, h] int64, -1 free
        self.widths: Optional[np.ndarray] = None  # [p, h] int32
        self.last_used: Optional[np.ndarray] = None  # [p, h] int64
        self.tick = 0

    @property
    def pad_slot(self) -> int:
        return self.h - 1

    # ---------------- capacity ----------------
    def _grow(self, h_new: int, w_new: int, unit: "CollectiveLedger") -> None:
        """Reallocate to (h_new, w_new), keeping mapped rows (slot
        indices are preserved — only the pad slot moves). A grow is a
        full re-upload, charged to ``bytes_uploaded`` at true payload
        widths."""
        p = self.p
        mirror = np.full((p, h_new, w_new), self.sentinel, np.int32)
        slot_ids = np.full((p, h_new), -1, np.int64)
        widths = np.zeros((p, h_new), np.int32)
        last_used = np.zeros((p, h_new), np.int64)
        if self.mirror is not None:
            keep = self.h - 1  # old data slots (old pad row is empty)
            mirror[:, :keep, : self.w] = self.mirror[:, :keep, :]
            slot_ids[:, :keep] = self.slot_ids[:, :keep]
            widths[:, :keep] = self.widths[:, :keep]
            last_used[:, :keep] = self.last_used[:, :keep]
            unit.bytes_uploaded += int(self.widths[:, :keep].sum()) * ID_BYTES
        self.mirror, self.slot_ids = mirror, slot_ids
        self.widths, self.last_used = widths, last_used
        self.h, self.w = h_new, w_new
        self._upload_full()

    def _upload_full(self) -> None:
        # straight from the host mirror onto each rank's device (no
        # staging of the whole buffer on device 0)
        self.device = jax.device_put(
            self.mirror, NamedSharding(self.mesh, P(self.axis))
        )

    def _alloc(self, k: int, protected: set) -> int:
        """A data slot for rank k: first free slot, else LRU-evict a
        slot the current unit does not reference. Capacity is grown
        ahead of assignment, so an evictable slot always exists."""
        ids = self.slot_ids[k, : self.h - 1]
        free = np.flatnonzero(ids < 0)
        if free.size:
            return int(free[0])
        lu = self.last_used[k, : self.h - 1].astype(np.int64, copy=True)
        if protected:
            lu[list(protected)] = np.iinfo(np.int64).max
        s = int(np.argmin(lu))
        assert s not in protected, "no evictable resident slot"
        old = int(self.slot_ids[k, s])
        del self.slot_of[k][old]
        return s

    # ---------------- per-unit patching ----------------
    def ensure(
        self,
        needed: List[Dict[int, np.ndarray]],
        unit: "CollectiveLedger",
    ) -> None:
        """Make every (rank, id) in ``needed`` resident: reuse mapped
        rows (``upload_bytes_saved``), patch the rest in one device
        scatter (``bytes_uploaded`` / ``n_patches``, span
        ``spmd_patch``)."""
        self.tick += 1
        p = self.p
        w_need = max((r.size for d in needed for r in d.values()), default=1)
        h_need = max((len(d) for d in needed), default=0) + 1
        grew = False
        if w_need > self.w or h_need > self.h:
            grew = True
            self._grow(
                max(self.h, pow2_ceil(h_need, 8)),
                max(self.w, pow2_ceil(w_need, 8)),
                unit,
            )
        patches: List[Tuple[int, int, np.ndarray]] = []
        for k in range(p):
            # reused slots are protected from this unit's evictions
            protected = {
                s
                for v, row in needed[k].items()
                if (s := self.slot_of[k].get(v)) is not None
                and self.widths[k, s] == row.size
            }
            for v, row in needed[k].items():
                s = self.slot_of[k].get(v)
                if s is not None and self.widths[k, s] == row.size:
                    # fresh by the invalidation contract — zero H2D.
                    # (a grow already charged this row to the full
                    # re-upload, so it is not "saved" this unit)
                    if not grew:
                        unit.upload_bytes_saved += row.size * ID_BYTES
                    self.last_used[k, s] = self.tick
                    continue
                if s is None:
                    s = self._alloc(k, protected)
                    self.slot_of[k][v] = s
                    self.slot_ids[k, s] = v
                protected.add(s)
                self.widths[k, s] = row.size
                self.last_used[k, s] = self.tick
                self.mirror[k, s, :] = self.sentinel
                self.mirror[k, s, : row.size] = row
                patches.append((k, s, row))
                unit.bytes_uploaded += row.size * ID_BYTES
                unit.n_patches += 1
        self._patch_device(patches, grew)

    def _patch_device(self, patches, grew: bool) -> None:
        if not patches:
            return
        with obs_trace.span(
            "spmd_patch", cat="spmd", n_patches=len(patches),
            patch_bytes=sum(r.size for _, _, r in patches) * ID_BYTES,
            rebuild=grew,
        ):
            if grew:
                # the grow already uploaded the full mirror; fold the
                # new rows into one more full upload (they were written
                # to the mirror above)
                self._upload_full()
                return
            # pad the scatter to a pow-2 row count so its compiled
            # shape space stays logarithmic; filler rows rewrite the
            # permanent pad slot with the sentinel it already holds
            m = pow2_ceil(len(patches))
            ks = np.zeros(m, np.int32)
            ss = np.full(m, self.pad_slot, np.int32)
            vals = np.full((m, self.w), self.sentinel, np.int32)
            for i, (k, s, row) in enumerate(patches):
                ks[i], ss[i] = k, s
                vals[i, : row.size] = row
            self.device = self.device.at[ks, ss].set(jnp.asarray(vals))

    # ---------------- coherence ----------------
    def invalidate(self, changed_ids=None) -> None:
        """Drop mutated ids from every rank's map (``None`` = drop
        everything, e.g. on a store swap). Slot contents become
        unreferenced garbage; no device traffic."""
        if self.mirror is None:
            return
        if changed_ids is None:
            for k in range(self.p):
                self.slot_of[k].clear()
            self.slot_ids[:, :] = -1
            self.widths[:, :] = 0
            return
        for v in np.unique(np.asarray(changed_ids, np.int64).ravel()):
            v = int(v)
            for k in range(self.p):
                s = self.slot_of[k].pop(v, None)
                if s is not None:
                    self.slot_ids[k, s] = -1
                    self.widths[k, s] = 0

    def audit(self, store, expect=None) -> int:
        """Number of mapped rows whose mirror content differs from the
        authoritative store — 0 under the invalidation contract.
        ``expect(k, key)`` (optional) maps a buffer key to its expected
        content; the default is ``store.row(key)`` (the executor passes
        a resolver that understands hub-fragment keys)."""
        bad = 0
        for k in range(self.p):
            for v, s in self.slot_of[k].items():
                row = (
                    expect(k, v)
                    if expect is not None
                    else np.asarray(store.row(v))
                )
                ok = self.widths[k, s] == row.size and np.array_equal(
                    self.mirror[k, s, : row.size], row
                )
                bad += 0 if ok else 1
        return bad


def _body_serve(
    rows,  # [1, H, W] this rank's resident row buffer (pad row last)
    serve_idx,  # [1, p, S_tot] resident slots shipped per requester
    *,
    axis: str,
    p: int,
    w: int,
    serve_cfg: Tuple[Tuple[int, int], ...],  # (s_b, w_b) per bucket
    f_pad: int,  # high-water fetched-block capacity (pow-2)
    sentinel: int,
):
    """Serve phase: one ``all_to_all`` per width rung — each ships its
    rung's rows at the rung width instead of the global max width.
    ``serve_cfg`` holds windowed high-water capacities, so the program
    recompiles only when a capacity moves, and ``bytes_on_wire`` is
    charged from these exact shapes. The received rows are padded into
    a fixed-capacity ``[1, f_pad, w]`` block so the downstream
    intersect program's input shape is stable across units."""
    # shard_map keeps the sharded leading axis at local size 1 — squeeze.
    rows = rows[0]
    serve_idx = serve_idx[0]
    parts = []
    off = 0
    for s_b, w_b in serve_cfg:
        idx = serve_idx[:, off : off + s_b]  # [p, s_b]
        to_send = rows[idx][:, :, :w_b]  # [p, s_b, w_b]
        got = jax.lax.all_to_all(
            to_send, axis, split_axis=0, concat_axis=0, tiled=False
        )
        fetched = got.reshape(p * s_b, w_b)
        if w_b < w:
            fetched = jnp.pad(
                fetched, ((0, 0), (0, w - w_b)), constant_values=sentinel
            )
        parts.append(fetched)
        off += s_b
    n_rows = sum(fp.shape[0] for fp in parts)
    parts.append(
        jnp.full((f_pad - n_rows, w), sentinel, rows.dtype)
    )
    return jnp.concatenate(parts, 0)[None]


def _body_pairs(
    rows,  # [1, H, W] this rank's resident row buffer (pad row last)
    fetched,  # [1, f_pad, W] the serve program's padded output block
    a_idx,  # [1, E_tot] combined-buffer index of each pair's A row
    b_idx,  # [1, E_tot]
    mask,  # [1, E_tot] real-pair mask
    *,
    p: int,
    w: int,
    pair_cfg: Tuple[Tuple[int, int, int], ...],  # (e_b, w_p, block_e)
    sentinel: int,
    use_kernel: bool,
    interpret: bool,
):
    """Intersect phase: one kernel call per pair width bucket, each
    comparing only w_p columns instead of the global max width. Shapes
    here are canonical (fixed bucket widths, high-water sizes), so this
    — the expensive program to compile — recompiles only when a
    high-water mark grows, not per unit."""
    rows = rows[0]
    fetched = fetched[0]
    a_idx = a_idx[0]
    b_idx = b_idx[0]
    mask = mask[0]
    combined = jnp.concatenate([rows, fetched], 0)
    outs = []
    off = 0
    for e_b, w_p, block_e in pair_cfg:
        ra = combined[a_idx[off : off + e_b]][:, :w_p]
        rb = combined[b_idx[off : off + e_b]][:, :w_p]
        if use_kernel:
            cnt = intersect_count(
                ra, rb, sentinel=sentinel, block_e=block_e,
                interpret=interpret,
            )
        else:
            cnt = count_bsearch_jnp(ra, rb, sentinel)
        outs.append(
            jnp.where(mask[off : off + e_b], cnt, 0).astype(jnp.int32)
        )
        off += e_b
    out = (
        jnp.concatenate(outs) if outs else jnp.zeros((0,), jnp.int32)
    )
    return out[None]


@dataclasses.dataclass
class PendingUnit:
    """An in-flight execution unit: the host-side ledger is final at
    dispatch (pack, patch, and ship accounting are synchronous), the
    device counts are not. ``wait()`` is the reconciliation barrier —
    the only ``block_until_ready`` in the SPMD path — and returns
    ``(counts, unit)`` exactly like the old blocking ``run()``."""

    executor: "SpmdIntersectExecutor"
    out: object  # device array, or None for the empty unit
    scatter: Optional[List[List[Tuple[np.ndarray, int]]]]
    pair_sizes: List[int]
    unit: CollectiveLedger
    t_dispatch: float
    _done: Optional[tuple] = None

    def wait(self):
        if self._done is not None:
            return self._done
        if self.out is None:  # empty unit — nothing was dispatched
            counts = [np.zeros(sz, np.int64) for sz in self.pair_sizes]
            self._done = (counts, self.unit)
            return self._done
        with obs_trace.span(
            "spmd_overlap_wait", cat="spmd", pairs=int(self.unit.n_pairs)
        ):
            t0 = time.perf_counter()
            arr = np.asarray(jax.block_until_ready(self.out), np.int64)
            t1 = time.perf_counter()
        waited = t1 - t0
        wall = t1 - self.t_dispatch
        self.unit.overlap_wait_s += waited
        self.unit.device_wall_s += wall
        led = self.executor.ledger
        led.overlap_wait_s += waited
        led.device_wall_s += wall
        counts = [np.zeros(sz, np.int64) for sz in self.pair_sizes]
        for j in range(self.executor.p):
            for positions, off in self.scatter[j]:
                # additive scatter: a pair against a split hub row
                # expands into one sub-pair per fragment, all mapped to
                # the same worklist position — fragments partition the
                # row, so summing the sub-counts IS the deterministic
                # fragment reduction (and reduces to plain assignment
                # when every position is unique, the non-hub case).
                np.add.at(
                    counts[j], positions,
                    arr[j, off : off + positions.size],
                )
        self._done = (counts, self.unit)
        return self._done


class SpmdIntersectExecutor:
    """Runs per-rank pair-intersection worklists as one ``shard_map``
    over a 1-D ``("rank",)`` mesh of ``p`` devices.

    One ``dispatch()`` call launches one execution unit: patch the
    persistent resident buffer with this unit's working-set drift, ship
    the remote misses with width-bucketed ``all_to_all`` collectives,
    and count every pair on its executing rank's device. The returned
    ``PendingUnit`` carries the complete measured ``CollectiveLedger``
    immediately; ``wait()`` blocks for the per-rank counts. ``run()``
    is the unpipelined dispatch+wait convenience."""

    def __init__(
        self,
        part,
        n: int,
        *,
        p: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        use_kernel: Optional[bool] = None,
        block_e: int = 128,
        interpret: Optional[bool] = None,
        axis: str = "rank",
        runtime=None,
    ):
        self.part = part
        self.n = int(n)
        self.p = int(p if p is not None else part.p)
        self.axis = axis
        if use_kernel is None:
            # compiled kernel on the chip, jnp binary search on the CPU
            use_kernel = not default_interpret()
        self.use_kernel = bool(use_kernel)
        if interpret is None:
            interpret = default_interpret()
        self.interpret = bool(interpret)
        self.block_e = int(block_e)
        if mesh is None:
            devs = jax.devices()
            if len(devs) < self.p:
                raise RuntimeError(
                    f"SPMD execution at p={self.p} needs {self.p} devices "
                    f"but only {len(devs)} {devs[0].platform} devices "
                    f"exist — call ensure_host_devices({self.p}) before "
                    "the first jax use"
                )
            mesh = Mesh(np.array(devs[: self.p]), (axis,))
        self.mesh = mesh
        self.ledger = CollectiveLedger.zero(self.p)
        self._buf = _ResidentShardBuffer(self.p, self.n, self.mesh, axis)
        self._fn_cache: dict = {}
        # windowed high-water capacities (keyed by rung width) that keep
        # both programs' shapes canonical across units — see _CAP_WINDOW
        self._f_hw = 1  # fetched-block capacity, pow-2, grow-only
        self._serve_s_seen: Dict[int, object] = {}  # rung w -> need deque
        self._pair_e_seen: Dict[int, object] = {}  # rung w -> need deque
        if runtime is not None:
            runtime.add_invalidation_listener(self.invalidate)

    # ---------------- coherence ----------------
    def invalidate(self, changed_ids=None) -> None:
        """Drop mutated ids from the resident buffer (``None`` = all).
        Wired to the runtime's coherence fanout by the engines; the
        streaming engine additionally notifies deletions mid-batch.
        Hub fragments live under synthetic keys ``n + 1 + v`` (see
        ``dispatch``), so a mutated row drops both its full-row and its
        fragment residency."""
        self._buf.invalidate(changed_ids)
        if changed_ids is not None:
            arr = np.unique(np.asarray(changed_ids, np.int64).ravel())
            if arr.size:
                self._buf.invalidate(arr + self.n + 1)

    def audit_resident(self, store) -> int:
        """Stale resident rows vs the authoritative store (0 expected).
        Fragment keys audit against the fragment of the current store
        row they are defined to mirror."""
        frag_base = self.n + 1
        part = self.part

        def expect(k: int, key: int) -> np.ndarray:
            if key >= frag_base:
                return part.fragment(
                    np.asarray(store.row(key - frag_base)), k
                )
            return np.asarray(store.row(key))

        return self._buf.audit(store, expect=expect)

    # ---------------- compiled-function caches ----------------
    # Two programs, split on purpose: the serve program re-shapes when
    # the wire capacities move, the expensive intersect program when the
    # pair capacities do — both follow windowed high-water marks, so in
    # steady state neither recompiles and dispatch is pure execution.
    def _fn_serve(self, h, w, serve_cfg, f_pad):
        key = ("serve", h, w, serve_cfg, f_pad)
        fn = self._fn_cache.get(key)
        if fn is None:
            body = functools.partial(
                _body_serve,
                axis=self.axis,
                p=self.p,
                w=w,
                serve_cfg=serve_cfg,
                f_pad=f_pad,
                sentinel=self.n,
            )
            sh = P(self.axis)
            fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(sh, sh),
                    out_specs=sh,
                    check_vma=False,
                )
            )
            self._fn_cache[key] = fn
        return fn

    def _fn_pairs(self, h, f_pad, w, pair_cfg):
        key = ("pairs", h, f_pad, w, pair_cfg)
        fn = self._fn_cache.get(key)
        if fn is None:
            body = functools.partial(
                _body_pairs,
                p=self.p,
                w=w,
                pair_cfg=pair_cfg,
                sentinel=self.n,
                use_kernel=self.use_kernel,
                interpret=self.interpret,
            )
            sh = P(self.axis)
            fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(sh, sh, sh, sh, sh),
                    out_specs=sh,
                    check_vma=False,
                )
            )
            self._fn_cache[key] = fn
        return fn

    def _empty_fetched(self, f_pad: int, w: int):
        """Cached all-sentinel fetch block for units with no serve
        traffic: the intersect program still takes its canonical
        ``[p, f_pad, w]`` fetch input, but nothing goes on the wire."""
        key = ("fetched0", f_pad, w)
        blk = self._fn_cache.get(key)
        if blk is None:
            blk = jax.device_put(
                jnp.full((self.p, f_pad, w), self.n, jnp.int32),
                NamedSharding(self.mesh, P(self.axis)),
            )
            self._fn_cache[key] = blk
        return blk

    def _pair_widths(self, w: int) -> List[int]:
        """Fixed geometric pow-2 pair-bucket widths for buffer width
        ``w`` (the ladder clipped to ``w``, so at most
        ``len(_PAIR_WIDTH_LADDER)`` buckets, last always ``w``). Fixed
        boundaries trade a bounded amount of compare padding (<4x
        within a bucket) for a canonical compiled shape set — the
        adaptive smallest-merge split would re-shape (and recompile)
        the intersect program nearly every unit."""
        return sorted({min(w, c) for c in _PAIR_WIDTH_LADDER})

    def _cap(self, seen: Dict[int, object], rung_w: int, need: int,
             lo: int) -> int:
        """Windowed pow-2 capacity for one rung: the pow-2 ceiling of
        the max need over the last ``_CAP_WINDOW`` units. Stable under
        per-unit jitter (no recompile), grows immediately when a unit
        needs more, and decays once an old peak leaves the window — so
        a converging workload stops paying (wire bytes and pad compute)
        for its warm-up spike."""
        dq = seen.get(rung_w)
        if dq is None:
            dq = seen[rung_w] = collections.deque(maxlen=_CAP_WINDOW)
        dq.append(int(need))
        return pow2_ceil(max(dq), lo)

    # ---------------- one execution unit ----------------
    def dispatch(self, shards: List[ShardWork], store) -> PendingUnit:
        """Pack, patch, and launch one unit without blocking. ``store``
        provides ``row(v)`` for the rows each owner serves (its
        authoritative shard content). The returned ``PendingUnit``'s
        ledger is complete immediately (and already folded into the
        cumulative ``self.ledger``, wall-clock fields excepted) — the
        measured-vs-modeled reconciliation can run before ``wait()``."""
        p = self.p
        assert len(shards) == p and all(
            s.rank == k for k, s in enumerate(shards)
        ), "need one ShardWork per rank, in rank order"
        unit = CollectiveLedger.zero(p)
        pair_sizes = [s.pair_a.size for s in shards]
        n_pairs = sum(pair_sizes)
        n_fetched = sum(len(s.fetched_ids) for s in shards)
        if n_pairs == 0 and n_fetched == 0:
            return PendingUnit(self, None, None, pair_sizes, unit, 0.0)

        # spans: host-side packing vs. the device collective, as two
        # sibling phases (manual open/close keeps the hot path unindented)
        _pack = obs_trace.span("spmd_pack", cat="spmd", n_pairs=n_pairs,
                               n_fetched=n_fetched)
        _pack.__enter__()

        # serve lists: ship[k][j] = buffer keys rank k sends requester
        # j, in requester fetch order (mirrors serve_rows accounting).
        # Keys are vertex ids for whole rows; a *split hub* row ships
        # as per-rank fragments under synthetic keys ``frag_base + v``
        # (frag_base = n + 1, so full-row and fragment residency never
        # collide): every rank with a nonempty fragment serves it, the
        # requester's own fragment stays rank-resident and free —
        # exactly the charges ``ShardedRuntime._charge_remote_miss``
        # models, so the reconciliation stays row-for-row.
        part = self.part
        hub_split = bool(getattr(part, "has_hubs", False))
        frag_base = self.n + 1
        ship: List[List[List[int]]] = [
            [[] for _ in range(p)] for _ in range(p)
        ]
        requested: List[set] = [set() for _ in range(p)]
        # full content of every fetched hub row (fragments slice it)
        hub_full: Dict[int, np.ndarray] = {}
        # requester -> fetched hub ids (their own-fragment residency)
        hub_fetched: List[List[int]] = [[] for _ in range(p)]
        for j, sh in enumerate(shards):
            for v in sh.fetched_ids:
                v = int(v)
                assert v not in sh.rows_held, (
                    f"id {v} both held and fetched at rank {j}"
                )
                k = int(part.owner(v))
                assert k != j, f"rank {j} fetching its own row {v}"
                if v in requested[j]:
                    continue  # one shipment per (owner, requester, id)
                requested[j].add(v)
                if hub_split and bool(part.is_hub(v)):
                    row = hub_full.get(v)
                    if row is None:
                        held = shards[k].rows_held.get(v)
                        row = np.asarray(
                            held if held is not None else store.row(v)
                        )
                        hub_full[v] = row
                    hub_fetched[j].append(v)
                    for q in range(p):
                        if q == j:
                            continue
                        if part.fragment(row, q).size == 0:
                            continue
                        ship[q][j].append(frag_base + v)
                else:
                    ship[k][j].append(v)

        # serve content: whole rows come from the serving rank's held
        # copy (else the authoritative store); fragment keys slice the
        # full hub row — every rank can serve its fragment because the
        # fragment IS rank q's share of the split row.
        serve_rows_content: List[Dict[int, np.ndarray]] = [
            {} for _ in range(p)
        ]
        for k in range(p):
            for j in range(p):
                for key in ship[k][j]:
                    if key not in serve_rows_content[k]:
                        if key >= frag_base:
                            row = part.fragment(
                                hub_full[key - frag_base], k
                            )
                        else:
                            held = shards[k].rows_held.get(key)
                            row = held if held is not None else np.asarray(
                                store.row(key)
                            )
                        serve_rows_content[k][key] = row
                    unit.rows_shipped[k, j] += 1
                    unit.bytes_payload += (
                        serve_rows_content[k][key].size * ID_BYTES
                    )

        # resident working set: held rows, the rows/fragments served
        # from this rank's buffer, and each requester's own fragment of
        # every hub row it fetched (local, never on the wire) —
        # already-resident entries cost zero H2D.
        needed: List[Dict[int, np.ndarray]] = []
        for k, sh in enumerate(shards):
            d = {int(v): np.asarray(row) for v, row in sh.rows_held.items()}
            for key, row in serve_rows_content[k].items():
                d.setdefault(key, row)
            for v in hub_fetched[k]:
                own = part.fragment(hub_full[v], k)
                if own.size:
                    d.setdefault(frag_base + v, own)
            needed.append(d)
        self._buf.ensure(needed, unit)
        h, w = self._buf.h, self._buf.w
        pad_slot = self._buf.pad_slot

        # per-unit max width (held + served), for the single-width
        # wire baseline the old non-bucketed collective would have paid
        w_unit = max((r.size for d in needed for r in d.values()), default=1)

        # ---- serve rungs: one all_to_all per ladder width class ----
        # Canonical shapes here too: the fixed geometric width ladder
        # (same as the pair buckets) and windowed per-rung count
        # capacities. Adaptive per-unit buckets shipped slightly fewer
        # wire bytes but re-shaped (and recompiled) the serve program
        # nearly every unit — on the measured profile that compile churn
        # was the entire SPMD-vs-loop gap. ``bytes_on_wire`` still
        # reports the actual shipped shapes, so the padding accounting
        # stays honest; the windowed decay keeps the capacities tracking
        # the workload instead of its historical peak.
        widths = self._pair_widths(w)
        serve_lists: List[Dict[Tuple[int, int], List[int]]] = [
            {} for _ in widths
        ]
        widths_arr = np.asarray(widths, np.int64)
        has_serve = False
        for k in range(p):
            for j in range(p):
                for key in ship[k][j]:
                    has_serve = True
                    rung = int(np.searchsorted(
                        widths_arr, max(serve_rows_content[k][key].size, 1),
                        side="left",
                    ))
                    serve_lists[rung].setdefault((k, j), []).append(key)
        serve_cfg: List[Tuple[int, int]] = []
        serve_segs: List[np.ndarray] = []
        # fetch_refs[j][key] -> every (combined-buffer index, width)
        # that arrived for ``key`` at requester j. Whole rows have one
        # ref; a split hub row has one ref per serving rank (its
        # fragments), all under the same ``frag_base + v`` key.
        fetch_refs: List[Dict[int, List[Tuple[int, int]]]] = [
            {} for _ in range(p)
        ]
        fetch_base = h
        wire_bytes = 0
        for rung, w_b in enumerate(widths):
            lists = serve_lists[rung]
            need = max((len(vs) for vs in lists.values()), default=0)
            s_b = self._cap(self._serve_s_seen, w_b, need, 1)
            # a unit with no serve traffic at all skips the collective
            # entirely (wire bytes 0, cached sentinel fetch block below)
            if not has_serve:
                continue
            seg = np.full((p, p, s_b), pad_slot, np.int32)
            for (k, j), keys in lists.items():
                for pos, key in enumerate(keys):
                    seg[k, j, pos] = self._buf.slot_of[k][key]
                    fetch_refs[j].setdefault(key, []).append((
                        fetch_base + k * s_b + pos,
                        serve_rows_content[k][key].size,
                    ))
            serve_cfg.append((s_b, w_b))
            serve_segs.append(seg)
            fetch_base += p * s_b
            wire_bytes += p * (p - 1) * s_b * w_b * ID_BYTES
        serve_idx = (
            np.concatenate(serve_segs, axis=2)
            if has_serve
            else np.zeros((p, p, 0), np.int32)
        )
        # single-width baseline: one collective padded to the max ship
        # count and the unit's max row width (the pre-bucketing scheme)
        s_single = pow2_ceil(
            max((len(ship[k][j]) for k in range(p) for j in range(p)),
                default=0),
            4,
        )
        # the baseline skips empty units too — it gets the same
        # no-traffic shortcut, so the comparison is padding-vs-padding
        single_bytes = (
            p * (p - 1) * s_single * pow2_ceil(w_unit, 1) * ID_BYTES
            if has_serve
            else 0
        )

        # ---- pair worklists, bucketed by pow-2 pair width ----
        # A pair references each side through its *refs*: the combined-
        # buffer indices (with true widths) covering that row as read by
        # rank j. Whole rows — held, served-from-own-buffer, or fetched
        # — have exactly one ref; a fetched split-hub row has one ref
        # per nonempty fragment (own fragment resident, the rest in the
        # fetch block). The pair expands into the cross product of its
        # sides' refs; fragments partition the row, so the sub-counts
        # sum to the whole-row intersection (the additive scatter in
        # ``PendingUnit.wait`` performs that reduction). Everything
        # reduces to one sub-pair per pair when no hub is split.
        def refs(j: int, v: int) -> List[Tuple[int, int]]:
            row = needed[j].get(v)
            if row is not None:
                return [(self._buf.slot_of[j][v], row.size)]
            out: List[Tuple[int, int]] = []
            own = needed[j].get(frag_base + v)
            if own is not None:
                out.append((self._buf.slot_of[j][frag_base + v],
                            own.size))
            out.extend(fetch_refs[j].get(frag_base + v, ()))
            out.extend(fetch_refs[j].get(v, ()))
            return out

        sub_rank: List[int] = []
        sub_pos: List[int] = []
        sub_a: List[int] = []
        sub_b: List[int] = []
        sub_w: List[int] = []
        for j, sh in enumerate(shards):
            for i in range(sh.pair_a.size):
                for ia, wa in refs(j, int(sh.pair_a[i])):
                    for ib, wb in refs(j, int(sh.pair_b[i])):
                        sub_rank.append(j)
                        sub_pos.append(i)
                        sub_a.append(ia)
                        sub_b.append(ib)
                        sub_w.append(max(wa, wb, 1))
        sub_rank = np.asarray(sub_rank, np.int64)
        sub_pos = np.asarray(sub_pos, np.int64)
        sub_a_arr = np.asarray(sub_a, np.int64)
        sub_b_arr = np.asarray(sub_b, np.int64)

        # the fetched block is padded to a grow-only pow-2 capacity so
        # the intersect program's input shape is unit-independent
        f_exact = fetch_base - h
        self._f_hw = max(self._f_hw, pow2_ceil(max(f_exact, 1)))
        f_pad = self._f_hw

        widths = self._pair_widths(w)
        sub_w_arr = np.maximum(np.asarray(sub_w, np.int64), 1)
        pair_slot = np.searchsorted(
            np.asarray(widths, np.int64), sub_w_arr, side="left"
        )
        pair_cfg: List[Tuple[int, int, int]] = []
        a_segs: List[np.ndarray] = []
        b_segs: List[np.ndarray] = []
        m_segs: List[np.ndarray] = []
        scatter: List[List[Tuple[np.ndarray, int]]] = [[] for _ in range(p)]
        seg_off = 0
        for slot, w_p in enumerate(widths):
            indices = np.flatnonzero(pair_slot == slot)
            e_max = (
                int(np.max(np.bincount(sub_rank[indices], minlength=p)))
                if indices.size
                else 0
            )
            # windowed per-rung capacity: the slot re-shapes (and the
            # intersect program recompiles) only when its windowed
            # high-water mark moves, never because this unit jitters
            e_pad = self._cap(self._pair_e_seen, w_p, e_max, 8)
            be = min(self.block_e, e_pad)
            a_seg = np.full((p, e_pad), pad_slot, np.int32)
            b_seg = np.full((p, e_pad), pad_slot, np.int32)
            m_seg = np.zeros((p, e_pad), bool)
            if indices.size:
                with obs_trace.span(
                    "intersect_kernel", cat="spmd", bucket_w=w_p,
                    pairs=int(indices.size),
                ):
                    for j in range(p):
                        sel = indices[sub_rank[indices] == j]
                        if not sel.size:
                            continue
                        a_seg[j, : sel.size] = sub_a_arr[sel]
                        b_seg[j, : sel.size] = sub_b_arr[sel]
                        m_seg[j, : sel.size] = True
                        scatter[j].append((sub_pos[sel], seg_off))
            pair_cfg.append((e_pad, w_p, be))
            a_segs.append(a_seg)
            b_segs.append(b_seg)
            m_segs.append(m_seg)
            seg_off += e_pad
        a_idx = np.concatenate(a_segs, axis=1)
        b_idx = np.concatenate(b_segs, axis=1)
        mask = np.concatenate(m_segs, axis=1)

        fn_s = (
            self._fn_serve(h, w, tuple(serve_cfg), f_pad)
            if has_serve
            else None
        )
        fn_p = self._fn_pairs(h, f_pad, w, tuple(pair_cfg))
        _pack.__exit__(None, None, None)
        if self.use_kernel:
            obs_trace.instant(
                "pallas_kernel", cat="kernel", kernel="spmd_pairs",
                interpret=self.interpret, pairs=int(a_idx.size),
                buckets=len(pair_cfg),
            )

        unit.n_collectives += 1 if has_serve else 0
        unit.n_pairs += n_pairs
        unit.bytes_on_wire += wire_bytes
        unit.bytes_on_wire_single += single_bytes
        t0 = time.perf_counter()
        # async launch — the span covers dispatch only; the device time
        # surfaces in spmd_overlap_wait at the reconciliation barrier.
        with obs_trace.span(
            "all_to_all", cat="spmd", pairs=n_pairs,
            payload_bytes=int(unit.bytes_payload), wire_bytes=wire_bytes,
            buckets=len(serve_cfg),
        ):
            fetched = (
                fn_s(self._buf.device, serve_idx)
                if has_serve
                else self._empty_fetched(f_pad, w)
            )
            out = fn_p(self._buf.device, fetched, a_idx, b_idx, mask)
        self.ledger.add(unit)  # wall-clock fields accrue at wait()
        return PendingUnit(self, out, scatter, pair_sizes, unit, t0)

    def run(self, shards: List[ShardWork], store):
        """Execute one unit synchronously (dispatch + wait). Returns
        ``(counts, ledger)``: per-rank int64 count arrays in worklist
        order and this unit's measured collective ledger (also folded
        into the cumulative ``self.ledger``)."""
        return self.dispatch(shards, store).wait()
