"""Mutable ``stream_ivf`` / ``stream_sharded`` backends.

Both subclass their read-only family backend and add host-side mutable
masters (numpy: delta tail, tombstone mask, id maps) mirrored to fixed-
shape device arrays after every mutation — the jitted search programs in
:mod:`repro.anns.stream.search` consume the mirrors, so insert/delete
change array *contents* only and never retrace.

Mutation contract (see :class:`repro.anns.api.MutableAnnsIndex`):

- ``insert(vectors, ids=None)`` — ids assigned sequentially when
  omitted; duplicate live ids are an error; a full tail raises
  :class:`DeltaTailFull` (call ``compact()``).  The sharded backend
  routes each vector to its nearest cell's owning shard and appends to
  that shard's tail — per-shard capacity, like every other per-shard
  array.
- ``delete(ids)`` — tombstones base entries via the position mask and
  tail entries by freeing the slot; returns the newly-dead count.
- ``compact()`` — survivors (base in cell-major order, then tail in
  slot order) are re-assigned against the *existing* centroids (plus
  the ``split_oversized`` cap invariant when the variant sets
  ``max_cell``) and laid out through
  :func:`repro.anns.ivf.layout.layout_from_assignments` — the same
  deterministic path as ``build_ivf``, so one mutation history always
  compacts to the same bytes.  Bumps ``epoch``; deltas recorded against
  an older epoch no longer apply.

Concurrency (the seqno fence): everything a jitted search consumes is
bundled into one immutable :class:`_SearchView` published by a single
reference assignment in ``_sync()``.  A search captures the view once
at entry and never touches backend attributes again, so a concurrent
mutation or compaction swap can never hand it a torn mix of old and new
state — it completes against the snapshot it started on.  ``compact()``
is two-phase: :meth:`_StreamCommon.prepare_compaction` snapshots the
survivors under the mutation lock and builds the replacement layout
*outside* it (a background worker — see
:class:`repro.anns.stream.compactor.BackgroundCompactor` — can run this
while serving continues), and :meth:`_StreamCommon.commit_compaction`
re-takes the lock, verifies the epoch fence, installs the new layout,
and replays the mutation journal that accumulated while the build ran.
Synchronous ``compact()`` is exactly prepare+commit with an empty
journal, so its bytes are unchanged.

Checkpointing: ``to_state_dict`` extends the family format with tail
leaves and packed tombstone bitmaps (``STATE_FORMAT`` bump; older
read-only snapshots still load, coming up with fresh mutable state);
``to_delta_dict``/``apply_delta_dict`` carry just the mutable leaves +
(``seqno``, ``epoch``) for ``repro.ckpt.save_index_delta``'s
incremental checkpoints.
"""
from __future__ import annotations

import dataclasses
import threading

import jax.numpy as jnp
import numpy as np

from repro.anns.api import SearchParams, SearchResult
from repro.anns.backends.ivf import IvfBackend, nprobe_for, round_nprobe, \
    shortlist_width
from repro.anns.backends.sharded import ShardedBackend
from repro.anns.filters import FilterError, UnknownAttribute, \
    check_attributes
from repro.anns.ivf.kmeans import assign, split_oversized
from repro.anns.ivf.layout import layout_from_assignments
from repro.anns.ivf.sharding import place_on_mesh, shard_ivf
from repro.anns.registry import register
from repro.anns.stream.search import (make_placed_stream_search,
                                      stream_ivf_search,
                                      stream_sharded_search)

DEFAULT_TAIL_CAP = 256


class DeltaTailFull(RuntimeError):
    """The fixed-capacity delta tail cannot hold the insert — compact()
    (or delete) to make room.  ``free`` says how many slots were left
    (for the sharded backend: in the shard the insert routed to)."""

    def __init__(self, msg: str, *, free: int = 0):
        super().__init__(msg)
        self.free = int(free)


class CompactionInFlight(RuntimeError):
    """``prepare_compaction`` was called while a previous prepared
    compaction has not been committed or abandoned — the mutation
    journal can only track one pending swap."""


class StaleCompaction(RuntimeError):
    """``commit_compaction`` was handed a prepared layout whose epoch
    fence no longer matches the backend (another compaction committed
    in between, or nothing is in flight).  The prepared state must be
    discarded and prepared again."""


class _SearchView:
    """Immutable snapshot of everything one jitted search consumes.

    Published by a single reference assignment (``self._view = ...``) —
    that assignment *is* the seqno fence: a search captures the view
    once at entry, so a concurrent ``_sync`` (mutation) or compaction
    swap can never hand it base arrays from one epoch and tail/mask
    arrays from another.
    """

    __slots__ = ("index", "live", "tail_vecs", "tail_live", "ids_ext",
                 "seqno", "epoch", "attrs", "tail_attrs")

    def __init__(self, index, live, tail_vecs, tail_live, ids_ext,
                 seqno: int, epoch: int, attrs=None, tail_attrs=None):
        self.index = index
        self.live = live
        self.tail_vecs = tail_vecs
        self.tail_live = tail_live
        self.ids_ext = ids_ext
        self.seqno = int(seqno)
        self.epoch = int(epoch)
        # attribute columns in the view's own geometry (base like `live`,
        # tail like `tail_live`), device-resident — a filtered search
        # derives its bitmask from the snapshot it captured, so a
        # concurrent mutation can never tear mask against arrays
        self.attrs = attrs
        self.tail_attrs = tail_attrs


def _view_filter_masks(view: _SearchView, predicate):
    """Compile ``predicate`` against a view's attribute columns into
    device bool masks (base geometry, tail geometry).  The masks AND
    into ``live`` / ``tail_live`` — the exact tombstone path — so the
    jitted stream searches need no new arguments and no retrace: a
    filtered call passes masks of the same shape/dtype as unfiltered
    ones."""
    if view.attrs is None:
        raise UnknownAttribute(
            f"filter on {predicate.attr!r} but the backend has no "
            f"attribute columns — set_attributes() after build")
    col = view.attrs.get(predicate.attr)
    if col is None:
        raise UnknownAttribute(
            f"unknown attribute {predicate.attr!r} — available columns: "
            f"{sorted(view.attrs)}")
    vals = jnp.asarray(np.asarray(predicate.values, np.int32))
    base_mask = (col[..., None] == vals).any(-1)
    tail_mask = (view.tail_attrs[predicate.attr][..., None] == vals).any(-1)
    return base_mask, tail_mask


@dataclasses.dataclass(frozen=True)
class PreparedCompaction:
    """Replacement layout built off the hot path by
    ``prepare_compaction`` plus the fence it was snapshotted under;
    ``commit_compaction`` refuses it if the backend's epoch moved.
    ``attrs`` is the surviving attribute columns remapped into the new
    layout's position space (rides the same permutation as the id
    remap), or None when no columns are configured."""
    index: object
    epoch: int
    seqno: int
    empty: bool
    attrs: object = None


def _pack_mask(mask: np.ndarray) -> np.ndarray:
    return np.packbits(np.asarray(mask, bool).reshape(-1))

def _unpack_mask(bits: np.ndarray, shape) -> np.ndarray:
    n = int(np.prod(shape))
    out = np.unpackbits(np.asarray(bits, np.uint8), count=n)
    return out.astype(bool).reshape(shape)


def _check_insert_ids(ids, m: int):
    ids = np.asarray(ids, np.int32).reshape(-1)
    if len(ids) != m:
        raise ValueError(f"{m} vectors but {len(ids)} ids")
    if np.any(ids < 0):
        raise ValueError("ids must be non-negative")
    if len(np.unique(ids)) != m:
        raise ValueError("duplicate ids within one insert batch")
    return ids


def exact_live_gt(backend, queries, k: int) -> np.ndarray:
    """Exact top-k *ids* over a mutable backend's current live set —
    the moving ground truth mutations invalidate ``Dataset.gt`` against.
    Brute force over ``live_vectors()``; rows are ids (not positions),
    -1 padded when fewer than k vectors are live."""
    from repro.kernels.distance.ref import distance_ref
    import jax

    vecs, ids = backend.live_vectors()
    queries = np.asarray(queries, np.float32)
    if len(vecs) == 0:
        return np.full((len(queries), k), -1, np.int32)
    kk = min(k, len(vecs))
    out = []
    b = jnp.asarray(vecs)
    for i in range(0, len(queries), 512):
        d = distance_ref(jnp.asarray(queries[i:i + 512]), b, backend.metric)
        _, idx = jax.lax.top_k(-d, kk)
        out.append(np.asarray(idx))
    rows = ids[np.concatenate(out, axis=0)]
    if kk < k:
        rows = np.concatenate(
            [rows, np.full((len(rows), k - kk), -1, np.int32)], axis=1)
    return rows.astype(np.int32)


class _StreamCommon:
    """Host-side mutable state shared by both streaming backends.

    Masters are plain numpy (the checkpoint/delta leaves); subclasses
    define the tail geometry (flat vs per-shard) via ``_tail_shape`` and
    rebuild device mirrors in ``_sync``.
    """

    def _variant_tail_cap(self) -> int:
        cap = getattr(self.variant, "tail_cap", 0) or DEFAULT_TAIL_CAP
        return max(1, int(cap))

    def _init_concurrency(self) -> None:
        """Mutation lock + pending-compaction state; called from
        ``__init__`` (before any build/restore can race)."""
        self._lock = threading.RLock()
        self._compacting = False
        self._mutation_log: list[tuple] = []
        self._view: _SearchView | None = None

    def _tail_shape(self) -> tuple:
        return self._tail_shape_for(self.index)

    def _init_mutable(self) -> None:
        """Fresh mutable state over the current built index (used after
        build() and when restoring a pre-streaming checkpoint)."""
        idx = self.index
        ids = np.asarray(idx.ids)
        d = int(idx.centroids.shape[1])
        shape = self._tail_shape()
        self._live = np.ones(idx.n, bool)
        self._tail_vecs = np.zeros(shape + (d,), np.float32)
        self._tail_ids = np.full(shape, -1, np.int32)
        self._tail_live = np.zeros(shape, bool)
        # attribute columns survive adoption of a read-only snapshot that
        # carried them (self.attributes set by the parent restore); a
        # fresh build() resets them to None before reaching here
        self._tail_attrs = (None if self.attributes is None else
                            {c: np.full(shape, -1, np.int32)
                             for c in self.attributes})
        self.seqno = 0
        self.epoch = 0
        self._next_id = int(ids.max(initial=-1)) + 1
        self._rebuild_maps()
        self._sync()

    def _rebuild_maps(self) -> None:
        ids = np.asarray(self.index.ids)
        self._id_pos = {int(i): p for p, i in enumerate(ids) if i >= 0}
        # tail map values are index tuples — (slot,) flat, (shard, slot)
        # per-shard — so one delete path serves both layouts
        self._tail_pos = {}
        for slot in zip(*np.nonzero(self._tail_ids >= 0)):
            self._tail_pos[int(self._tail_ids[slot])] = slot

    # -- attribute columns -------------------------------------------------
    def set_attributes(self, attrs) -> None:
        """Attach per-vector attribute columns to a *freshly built*
        index — before any mutation, while the position->build-row map
        (``index.ids``) still describes the build base.  From then on
        ``insert(..., attrs=...)`` carries attributes forward, deletes
        free them with their slot, and ``compact()`` remaps the column
        through the same permutation as the id remap."""
        with self._lock:
            if self.seqno != 0 or self.epoch != 0 or self._compacting:
                raise FilterError(
                    "set_attributes must run on a freshly built index, "
                    "before any mutation — attributes then ride inserts "
                    "and compactions")
            super().set_attributes(attrs)      # stored in position space
            self._tail_attrs = {c: np.full(self._tail_shape(), -1,
                                           np.int32)
                                for c in self.attributes}
            self._sync()

    def live_attributes(self):
        """Attribute rows of everything live, in ``live_vectors()``
        order (base live positions, then tail slots) — the numpy-mirror
        counterpart the lifecycle property tests compare against.  None
        when no columns are configured."""
        with self._lock:
            if self.attributes is None:
                return None
            live_pos = np.flatnonzero(self._live)
            tail_slots = np.nonzero(self._tail_live)
            return {c: np.concatenate(
                        [np.asarray(self.attributes[c])[live_pos],
                         self._tail_attrs[c][tail_slots]]).astype(np.int32)
                    for c in self.attributes}

    def _normalize_insert_attrs(self, attrs, m: int):
        """Validate one insert batch's attribute values into
        ``{col: (m,) int32}`` covering every configured column (missing
        columns fill with the -1 "unattributed" sentinel).  Typed
        failures: attributes on an attribute-less backend, unknown
        column names, wrong length/dtype."""
        if attrs is None:
            if self.attributes is None:
                return None
            return {c: np.full(m, -1, np.int32) for c in self.attributes}
        if self.attributes is None:
            raise UnknownAttribute(
                "insert() got attribute values but the backend has no "
                "attribute columns — set_attributes() on the built "
                "index first")
        unknown = set(attrs) - set(self.attributes)
        if unknown:
            raise UnknownAttribute(
                f"insert() got unknown attribute columns "
                f"{sorted(unknown)} — configured: "
                f"{sorted(self.attributes)}")
        cols = check_attributes(dict(attrs), m)
        return {c: cols.get(c, np.full(m, -1, np.int32))
                for c in self.attributes}

    # -- MutableAnnsIndex protocol ----------------------------------------
    def n_live(self) -> int:
        with self._lock:
            return int(self._live.sum()) + int(self._tail_live.sum())

    def tail_fraction(self) -> float:
        with self._lock:
            tail = int(self._tail_live.sum())
            return tail / max(int(self._live.sum()) + tail, 1)

    def _apply_delete(self, ids_arr: np.ndarray) -> int:
        """Tombstone one id batch against the current maps — no lock, no
        seqno, no sync; the shared body of ``delete`` and journal
        replay."""
        count = 0
        for i in ids_arr.reshape(-1).tolist():
            i = int(i)
            p = self._id_pos.get(i)
            if p is not None and self._live[p]:
                self._live[p] = False
                count += 1
                continue
            s = self._tail_pos.pop(i, None)
            if s is not None:
                self._tail_live[s] = False
                self._tail_ids[s] = -1
                if self._tail_attrs is not None:
                    for col in self._tail_attrs.values():
                        col[s] = -1       # freed slots are byte-stable
                count += 1
        return count

    def delete(self, ids) -> int:
        assert self.index is not None, "build() first"
        ids_arr = np.asarray(ids)
        with self._lock:
            if self._compacting:
                self._mutation_log.append(("delete", ids_arr.copy()))
            count = self._apply_delete(ids_arr)
            self.seqno += 1
            self._sync()
        return count

    def insert(self, vectors, ids=None, attrs=None) -> np.ndarray:
        assert self.index is not None, "build() first"
        vecs = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if vecs.ndim == 1:
            vecs = vecs[None]
        m = len(vecs)
        with self._lock:
            acols = self._normalize_insert_attrs(attrs, m)
            if ids is None:
                ids = np.arange(self._next_id, self._next_id + m,
                                dtype=np.int32)
            ids = _check_insert_ids(ids, m)
            for i in ids.tolist():
                p = self._id_pos.get(int(i))
                if ((p is not None and self._live[p])
                        or int(i) in self._tail_pos):
                    raise ValueError(
                        f"id {int(i)} is already live — delete it "
                        f"first or pick a fresh id")
            self._place_in_tail(vecs, ids, acols)  # validates cap, fills
            if self._compacting:
                self._mutation_log.append((
                    "insert", vecs.copy(), ids.copy(),
                    None if acols is None else
                    {c: a.copy() for c, a in acols.items()}))
            self._next_id = max(self._next_id, int(ids.max()) + 1)
            self.seqno += 1
            self._sync()
        return ids

    def compact(self) -> None:
        """Fold tail + tombstones into a fresh cell-major layout against
        the existing centroids; see the module docstring.  An all-dead
        index keeps a single masked dummy row (the layout needs >= 1
        vector; it can never surface — its ``live`` bit stays False).

        Synchronous form of the two-phase path: prepare + commit with
        nothing able to land in the journal in between."""
        self.commit_compaction(self.prepare_compaction())

    def prepare_compaction(self) -> PreparedCompaction:
        """Phase one: snapshot the survivors under the lock, then build
        the replacement cell-major layout *outside* it — the expensive
        half (assign, split, layout, id remap, re-shard/placement) that
        a background worker runs while serving continues.  Mutations
        that land meanwhile are journaled and replayed at commit."""
        assert self.index is not None, "build() first"
        with self._lock:
            if self._compacting:
                raise CompactionInFlight(
                    "a prepared compaction is already pending — commit "
                    "or abandon it before preparing another")
            index = self.index
            vecs, oids = self.live_vectors()
            acols = self.live_attributes()
            fence_seqno, fence_epoch = self.seqno, self.epoch
            self._compacting = True
            self._mutation_log = []
        try:
            empty = len(vecs) == 0
            if empty:
                d = int(np.asarray(index.centroids).shape[1])
                vecs = np.zeros((1, d), np.float32)
                oids = np.array([-1], np.int32)
                if acols is not None:
                    acols = {c: np.array([-1], np.int32) for c in acols}
            centroids = np.asarray(index.centroids)
            a, _ = assign(vecs, centroids, metric=self.metric)
            max_cell = getattr(self.variant, "max_cell", 0) or None
            if max_cell:
                centroids, a = split_oversized(vecs, centroids, a,
                                               cap=max_cell)
            inner = layout_from_assignments(vecs, a, centroids,
                                            metric=self.metric)
            # inner.ids maps positions -> rows of `vecs`; compose the
            # surviving original ids on top, and carry the attribute
            # columns through the *same* permutation into the new
            # layout's position space
            perm = np.asarray(inner.ids)
            inner = dataclasses.replace(inner, ids=jnp.asarray(oids[perm]))
            new_attrs = (None if acols is None else
                         {c: np.ascontiguousarray(a_[perm], np.int32)
                          for c, a_ in acols.items()})
            return PreparedCompaction(
                index=self._finalize_layout(inner), epoch=fence_epoch,
                seqno=fence_seqno, empty=empty, attrs=new_attrs)
        except BaseException:
            with self._lock:
                self._compacting = False
                self._mutation_log = []
            raise

    def commit_compaction(self, prepared: PreparedCompaction) -> None:
        """Phase two: the fenced swap.  Under the lock — so no search
        can capture a half-installed view and no mutation can land
        mid-swap — verify the epoch fence, install the prepared layout,
        reset tail + tombstones, bump ``epoch``/``seqno``, and replay
        the journal of mutations that arrived during the build (in
        arrival order, so the replayed tail can never exceed the
        capacity the originals respected)."""
        with self._lock:
            if not self._compacting:
                raise StaleCompaction(
                    "no compaction is in flight — the prepared state "
                    "was already committed or abandoned")
            if prepared.epoch != self.epoch:
                self._compacting = False
                self._mutation_log = []
                raise StaleCompaction(
                    f"prepared at epoch {prepared.epoch}, but the "
                    f"backend is at epoch {self.epoch} — prepare again")
            log, self._mutation_log = self._mutation_log, []
            self._compacting = False
            self.index = prepared.index
            self.attributes = prepared.attrs
            self._clear_filter_caches()   # masks describe the old layout
            self._live = np.ones(self.index.n, bool)
            if prepared.empty:
                self._live[:] = False
            # fresh arrays, NOT in-place zeroing: published views hold
            # zero-copy jnp aliases of these buffers on CPU, and an
            # in-flight search on the old epoch must keep seeing the
            # tail vectors it captured
            self._tail_vecs = np.zeros_like(self._tail_vecs)
            self._tail_ids = np.full_like(self._tail_ids, -1)
            self._tail_live = np.zeros_like(self._tail_live)
            self._tail_attrs = (None if self.attributes is None else
                                {c: np.full(self._tail_shape(), -1,
                                            np.int32)
                                 for c in self.attributes})
            self.epoch += 1
            self.seqno += 1
            self._rebuild_maps()
            for entry in log:
                if entry[0] == "insert":
                    _, vecs, ids, acols = entry
                    self._place_in_tail(vecs, ids, acols)
                else:
                    self._apply_delete(entry[1])
            self._sync()

    def live_vectors(self) -> tuple[np.ndarray, np.ndarray]:
        """(L, d) fp32 vectors + (L,) int32 ids of everything currently
        visible to search, base (cell-major order) then tail (slot
        order) — the exact-reference counterpart of one search."""
        with self._lock:
            base, ids_arr = self._global_base()
            live_pos = np.flatnonzero(self._live)
            tail_slots = np.nonzero(self._tail_live)
            vecs = np.concatenate(
                [base[live_pos], self._tail_vecs[tail_slots]], axis=0)
            ids = np.concatenate(
                [ids_arr[live_pos],
                 self._tail_ids[tail_slots]]).astype(np.int32)
        return vecs, ids

    # -- warm-before-publish ----------------------------------------------
    def warm_compacted(self, prepared: PreparedCompaction, queries,
                       params: SearchParams) -> None:
        """Compile (and run once) the search program the prepared layout
        will serve after the swap, on the caller's thread — a background
        compactor calls this right before ``commit_compaction`` so the
        serving thread's first post-swap batch hits a warm jit cache
        instead of paying the recompile stall inline.  Contents of the
        throwaway view are irrelevant; only shapes/placement key the
        cache, and they match the post-swap state exactly."""
        import jax
        res = self._search_view(self._fresh_view(prepared.index),
                                queries, params)
        jax.block_until_ready(res.ids)

    def _fresh_view(self, index) -> _SearchView:
        """A view over ``index`` with an all-live base and an empty tail
        — the state ``commit_compaction`` publishes (pre-replay).
        Throwaway attribute columns ride along when the backend has any,
        so warming a *filtered* operating point compiles too (mask
        contents are irrelevant to the jit cache, only shapes are)."""
        d = int(np.asarray(index.centroids).shape[1])
        shape = self._tail_shape_for(index)
        attrs = tail_attrs = None
        if self.attributes is not None:
            attrs = {c: np.full(index.n, -1, np.int32)
                     for c in self.attributes}
            tail_attrs = {c: np.full(shape, -1, np.int32)
                          for c in self.attributes}
        return self._make_view(index, np.ones(index.n, bool),
                               np.zeros(shape + (d,), np.float32),
                               np.full(shape, -1, np.int32),
                               np.zeros(shape, bool), -1, -1,
                               attrs, tail_attrs)

    # -- mutable-state (de)serialization ----------------------------------
    def _mutable_leaves(self) -> dict:
        with self._lock:
            leaves = {"live_bits": _pack_mask(self._live),
                      "seqno": int(self.seqno), "epoch": int(self.epoch),
                      "next_id": int(self._next_id),
                      "tail_cap": int(self.tail_cap)}
            leaves.update(self._tail_leaves())
            if self._tail_attrs is not None:
                for c, a in self._tail_attrs.items():
                    leaves[f"tail_attr/{c}"] = a.copy()
        return leaves

    def _restore_mutable(self, state: dict) -> None:
        with self._lock:
            self.tail_cap = int(state.get("tail_cap", self.tail_cap))
            self._live = _unpack_mask(state["live_bits"], (self.index.n,))
            self._restore_tail_leaves(state)
            cols = {k.split("/", 1)[1]: np.ascontiguousarray(v, np.int32)
                    for k, v in state.items()
                    if k.startswith("tail_attr/")}
            if cols:
                self._tail_attrs = cols
            elif self.attributes is not None:
                # base carried attr columns but the delta predates them
                # (or a fresh tail): every slot is unattributed
                self._tail_attrs = {c: np.full(self._tail_shape(), -1,
                                               np.int32)
                                    for c in self.attributes}
            else:
                self._tail_attrs = None
            self.seqno = int(state["seqno"])
            self.epoch = int(state["epoch"])
            self._next_id = int(state["next_id"])
            self._rebuild_maps()
            self._sync()

    def to_delta_dict(self) -> dict:
        """Cumulative mutable-state snapshot since the base epoch: tail
        leaves + tombstone bitmap + (seqno, epoch).  Applying the latest
        delta reproduces the live state exactly; deltas are tiny next to
        the base (O(tail_cap * d) + N/8 bitmap bytes)."""
        assert self.index is not None, "build() first"
        return {"backend": self.name, **self._mutable_leaves()}

    def apply_delta_dict(self, delta: dict) -> None:
        """Replay one delta onto the restored base.  The delta must have
        been recorded against this base's compaction epoch — a stale
        delta (pre-compaction tail layout) cannot be replayed."""
        assert self.index is not None, "restore the base first"
        d_epoch = int(delta["epoch"])
        if d_epoch != self.epoch:
            raise ValueError(
                f"checkpoint delta was recorded at epoch {d_epoch}, but "
                f"the base is at epoch {self.epoch} — deltas do not span "
                f"compactions; re-save the base")
        self._restore_mutable({**delta, "tail_cap": self.tail_cap})


@register("stream_ivf")
class StreamingIvfBackend(_StreamCommon, IvfBackend):
    """Mutable single-device IVF: flat (cap, d) delta tail."""

    name = "stream_ivf"
    #: v1 = the read-only ivf layout (no stamp); v2 adds tail leaves +
    #: tombstone bitmaps + mutation counters; v3 adds optional attribute
    #: columns (attr/<col> base + tail_attr/<col> tail).  v1/v2 load.
    STATE_FORMAT = 3

    def __init__(self, variant=None, *, metric: str = "l2", seed: int = 0):
        if variant is None:
            from repro.anns.engine import VariantConfig
            variant = VariantConfig(backend=self.name)
        IvfBackend.__init__(self, variant, metric=metric, seed=seed)
        self.tail_cap = self._variant_tail_cap()
        self._init_concurrency()

    def _tail_shape_for(self, index) -> tuple:
        return (self.tail_cap,)

    def _with_blocks(self, index):
        return index        # stream_ivf_search scans base_q itself

    def _global_base(self):
        return (np.asarray(self.index.base),
                np.asarray(self.index.ids))

    def build(self, base: np.ndarray):
        out = IvfBackend.build(self, base)
        self._init_mutable()
        return out

    def _finalize_layout(self, inner):
        return inner

    def _place_in_tail(self, vecs: np.ndarray, ids: np.ndarray,
                       attrs=None) -> None:
        free = np.flatnonzero(self._tail_ids < 0)
        if len(free) < len(vecs):
            raise DeltaTailFull(
                f"delta tail has {len(free)} free slots of {self.tail_cap}, "
                f"cannot insert {len(vecs)} vectors — compact() first",
                free=len(free))
        slots = free[: len(vecs)]
        self._tail_vecs[slots] = vecs
        self._tail_ids[slots] = ids
        self._tail_live[slots] = True
        if attrs is not None:
            for c, col in attrs.items():
                self._tail_attrs[c][slots] = col
        for s, i in zip(slots.tolist(), ids.tolist()):
            self._tail_pos[int(i)] = (int(s),)

    def _make_view(self, index, live, tail_vecs, tail_ids, tail_live,
                   seqno, epoch, attrs=None, tail_attrs=None) -> _SearchView:
        dattrs = dtail = None
        if attrs is not None:
            dattrs = {c: jnp.asarray(a) for c, a in attrs.items()}
            dtail = {c: jnp.asarray(tail_attrs[c]) for c in attrs}
        return _SearchView(index, jnp.asarray(live),
                           jnp.asarray(tail_vecs), jnp.asarray(tail_live),
                           jnp.concatenate([index.ids,
                                            jnp.asarray(tail_ids)]),
                           seqno, epoch, dattrs, dtail)

    def _sync(self) -> None:
        """Publish a fresh immutable view of the fixed-shape device
        mirrors the jitted search consumes.  Shapes never change across
        mutations — no retrace; the single reference assignment is the
        fence concurrent searches read through."""
        self._view = self._make_view(self.index, self._live,
                                     self._tail_vecs, self._tail_ids,
                                     self._tail_live, self.seqno,
                                     self.epoch, self.attributes,
                                     self._tail_attrs)

    def search(self, queries, params: SearchParams) -> SearchResult:
        assert self.index is not None, "build() first"
        return self._search_view(self._view, queries, params)

    def _search_view(self, view: _SearchView, queries,
                     params: SearchParams) -> SearchResult:
        idx = view.index
        p = params.resolved(self.variant)
        # fixed output shape across mutations: clamp to the layout's
        # capacity (base rows + tail slots); short rows pad with id -1
        k = min(p.k, idx.n + self.tail_cap)
        k_base = min(k, idx.n)
        nprobe = nprobe_for(self.variant, p, idx.nlist)
        min_probe = idx.min_cells_for(k_base)
        if nprobe < min_probe:
            nprobe = min(round_nprobe(min_probe), idx.nlist)
        m = shortlist_width(p, k_base, idx.n, nprobe, idx.cell_pad)
        quantized = True if params.quantized is None else bool(params.quantized)
        live, tail_live = view.live, view.tail_live
        if p.filter is not None:
            # the filter rides the tombstone masks: same shapes, same
            # jitted program, zero new retrace buckets
            base_mask, tail_mask = _view_filter_masks(view, p.filter)
            live = live & base_mask
            tail_live = tail_live & tail_mask
        out_ids, out_d, scanned = stream_ivf_search(
            idx.centroids, idx.cells, idx.base, idx.base_q, idx.scales,
            live, view.tail_vecs, tail_live,
            view.ids_ext, jnp.asarray(queries, jnp.float32),
            nprobe=nprobe, k=k, m=m, metric=self.metric, quantized=quantized)
        return SearchResult(ids=out_ids, dists=out_d, steps=nprobe,
                            expansions=scanned, backend=self.name)

    def memory_bytes(self) -> int:
        extra = 0
        if self.index is not None:
            extra = (self._tail_vecs.nbytes + self._tail_ids.nbytes
                     + self._tail_live.nbytes + self._live.nbytes)
        return IvfBackend.memory_bytes(self) + extra

    def _tail_leaves(self) -> dict:
        return {"tail_vecs": self._tail_vecs.copy(),
                "tail_ids": self._tail_ids.copy(),
                "tail_live_bits": _pack_mask(self._tail_live)}

    def _restore_tail_leaves(self, state: dict) -> None:
        self._tail_vecs = np.asarray(state["tail_vecs"],
                                     np.float32).copy()
        self._tail_ids = np.asarray(state["tail_ids"], np.int32).copy()
        self._tail_live = _unpack_mask(state["tail_live_bits"],
                                       self._tail_ids.shape)
        self.tail_cap = int(self._tail_ids.shape[0])

    def to_state_dict(self) -> dict:
        st = IvfBackend.to_state_dict(self)
        st["backend"] = self.name
        st["state_format"] = self.STATE_FORMAT
        st.update(self._mutable_leaves())
        return st

    def from_state_dict(self, state: dict) -> None:
        IvfBackend.from_state_dict(self, state)
        if int(state.get("state_format", 1)) >= 2:
            self._restore_mutable(state)
        else:
            # a read-only ivf snapshot: adopt it with fresh mutable state
            self._init_mutable()


@register("stream_sharded")
class StreamingShardedBackend(_StreamCommon, ShardedBackend):
    """Mutable cell-routed sharded IVF: per-shard (S, cap, d) tails.

    Inserts route through the coarse quantizer to the owning shard's
    tail, so the mutable leaves shard exactly like the base slices (no
    replicated mutable state; the placed search gathers only (S, B, cap)
    tail scores on top of the read-only merge traffic).
    """

    name = "stream_sharded"
    #: v2 = the read-only shardN/base_f layout; v3 adds per-shard tail
    #: leaves + tombstone bitmaps + mutation counters; v4 adds optional
    #: attribute columns (attr/<col> + tail_attr/<col>).  v1-v3 load.
    STATE_FORMAT = 4

    def __init__(self, variant=None, *, metric: str = "l2", seed: int = 0):
        if variant is None:
            from repro.anns.engine import VariantConfig
            variant = VariantConfig(backend=self.name)
        ShardedBackend.__init__(self, variant, metric=metric, seed=seed)
        self.tail_cap = self._variant_tail_cap()
        self._mesh = None
        self._init_concurrency()

    def _tail_shape_for(self, index) -> tuple:
        return (index.n_shards, self.tail_cap)

    def _global_base(self):
        idx = self.index
        vb = np.asarray(idx.vec_bounds)
        bf = np.asarray(idx.base_f)
        parts = [bf[j, : int(vb[j + 1] - vb[j])]
                 for j in range(idx.n_shards)]
        return np.concatenate(parts, axis=0), np.asarray(idx.ids)

    def build(self, base: np.ndarray):
        out = ShardedBackend.build(self, base)
        self._init_mutable()
        return out

    def _finalize_layout(self, inner):
        """Re-shard + re-place happen in *prepare* (off the hot path):
        they are the expensive, device-touching half of the swap."""
        sharded = shard_ivf(inner, self.index.n_shards)
        if self._mesh is not None:
            sharded = place_on_mesh(sharded, self._mesh)
        return sharded

    def place_on_mesh(self, mesh) -> None:
        ShardedBackend.place_on_mesh(self, mesh)
        self._mesh = mesh
        self._placed_search = make_placed_stream_search(mesh)
        self._sync()

    def _route_to_shards(self, vecs: np.ndarray) -> np.ndarray:
        """Owning shard per vector: nearest cell through the existing
        coarse quantizer, then the static cell->shard map — the same
        routing one of these vectors gets at search time."""
        idx = self.index
        a, _ = assign(vecs, np.asarray(idx.centroids), metric=self.metric)
        return np.asarray(idx.cell_shard)[a]

    def _place_in_tail(self, vecs: np.ndarray, ids: np.ndarray,
                       attrs=None) -> None:
        shard_of = self._route_to_shards(vecs)
        frees = {}
        for j in np.unique(shard_of).tolist():
            need = int((shard_of == j).sum())
            free = np.flatnonzero(self._tail_ids[j] < 0)
            if len(free) < need:
                raise DeltaTailFull(
                    f"shard {j}'s delta tail has {len(free)} free slots "
                    f"of {self.tail_cap}, cannot take {need} routed "
                    f"vectors — compact() first", free=len(free))
            frees[j] = free
        used = {j: 0 for j in frees}
        for r, j in enumerate(shard_of.tolist()):
            s = int(frees[j][used[j]])
            used[j] += 1
            self._tail_vecs[j, s] = vecs[r]
            self._tail_ids[j, s] = ids[r]
            self._tail_live[j, s] = True
            if attrs is not None:
                for c in attrs:
                    self._tail_attrs[c][j, s] = attrs[c][r]
            self._tail_pos[int(ids[r])] = (j, s)

    def _make_view(self, index, live_global, tail_vecs, tail_ids,
                   tail_live, seqno, epoch, attrs=None,
                   tail_attrs=None) -> _SearchView:
        """Device view over ``index``: the global live mask expands to
        the per-shard padded layout; when mesh-placed, the mutable
        leaves are sharded along the same ``"shard"`` axis as the base
        slices and ``ids_ext`` stays replicated.  Attribute columns
        (global position space) expand exactly like ``live`` — pad rows
        take the -1 sentinel, which no predicate over real values
        matches."""
        vb = np.asarray(index.vec_bounds)
        npad = int(index.base_q.shape[1])
        live = np.zeros((index.n_shards, npad), bool)
        for j in range(index.n_shards):
            v0, v1 = int(vb[j]), int(vb[j + 1])
            live[j, : v1 - v0] = live_global[v0:v1]
        a_sh = None
        if attrs is not None:
            a_sh = {}
            for c, col in attrs.items():
                col = np.asarray(col)
                exp = np.full((index.n_shards, npad), -1, np.int32)
                for j in range(index.n_shards):
                    v0, v1 = int(vb[j]), int(vb[j + 1])
                    exp[j, : v1 - v0] = col[v0:v1]
                a_sh[c] = exp
        ids_ext = np.concatenate(
            [np.asarray(index.ids), np.asarray(tail_ids).reshape(-1)])
        if self._mesh is None:
            return _SearchView(
                index, jnp.asarray(live), jnp.asarray(tail_vecs),
                jnp.asarray(tail_live), jnp.asarray(ids_ext), seqno, epoch,
                None if a_sh is None else
                {c: jnp.asarray(a) for c, a in a_sh.items()},
                None if tail_attrs is None else
                {c: jnp.asarray(a) for c, a in tail_attrs.items()})
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(x, spec):
            return jax.device_put(jnp.asarray(x),
                                  NamedSharding(self._mesh, spec))
        return _SearchView(
            index, put(live, P("shard", None)),
            put(tail_vecs, P("shard", None, None)),
            put(tail_live, P("shard", None)),
            put(ids_ext, P()), seqno, epoch,
            None if a_sh is None else
            {c: put(a, P("shard", None)) for c, a in a_sh.items()},
            None if tail_attrs is None else
            {c: put(a, P("shard", None)) for c, a in tail_attrs.items()})

    def _sync(self) -> None:
        """Publish a fresh immutable view (see the ivf counterpart)."""
        self._view = self._make_view(self.index, self._live,
                                     self._tail_vecs, self._tail_ids,
                                     self._tail_live, self.seqno,
                                     self.epoch, self.attributes,
                                     self._tail_attrs)

    def _view_invocation(self, view: _SearchView, queries,
                         params: SearchParams):
        idx = view.index
        p = params.resolved(self.variant)
        k = min(p.k, idx.n + idx.n_shards * self.tail_cap)
        k_base = min(k, idx.n)
        nprobe = nprobe_for(self.variant, p, idx.nlist)
        min_probe = idx.min_cells_for(k_base)
        if nprobe < min_probe:
            nprobe = min(round_nprobe(min_probe), idx.nlist)
        m = shortlist_width(p, k_base, idx.n, nprobe, idx.cell_pad)
        quantized = True if params.quantized is None else bool(params.quantized)
        live, tail_live = view.live, view.tail_live
        if p.filter is not None:
            # predicate masks AND into the pad/tombstone liveness masks
            # host-side: same shapes and dtypes, so no new jit trace
            base_mask, tail_mask = _view_filter_masks(view, p.filter)
            live = live & base_mask
            tail_live = tail_live & tail_mask
        args = (idx.centroids, idx.cell_shard, idx.cell_row, idx.cells,
                idx.vec_start, idx.base_q, idx.scales, idx.base_f,
                live, view.tail_vecs, tail_live,
                view.ids_ext, jnp.asarray(queries, jnp.float32))
        statics = dict(nprobe=nprobe, k=k, m=m, metric=self.metric,
                       quantized=quantized)
        return args, statics

    def _invocation(self, queries, params: SearchParams):
        return self._view_invocation(self._view, queries, params)

    def _search_fn(self):
        return self._placed_search or stream_sharded_search

    def search(self, queries, params: SearchParams) -> SearchResult:
        assert self.index is not None, "build() first"
        return self._search_view(self._view, queries, params)

    def _search_view(self, view: _SearchView, queries,
                     params: SearchParams) -> SearchResult:
        args, statics = self._view_invocation(view, queries, params)
        out_ids, out_d, scanned = self._search_fn()(*args, **statics)
        return SearchResult(ids=out_ids, dists=out_d,
                            steps=statics["nprobe"],
                            expansions=scanned, backend=self.name)

    def memory_bytes(self) -> int:
        extra = 0
        if self.index is not None:
            extra = (self._tail_vecs.nbytes + self._tail_ids.nbytes
                     + self._tail_live.nbytes + self._live.nbytes)
        return ShardedBackend.memory_bytes(self) + extra

    def device_memory_bytes(self) -> int:
        if self.index is None:
            return 0
        extra = ((self._tail_vecs.nbytes + self._tail_ids.nbytes
                  + self._tail_live.nbytes + self._live.nbytes)
                 // max(self.index.n_shards, 1))
        return ShardedBackend.device_memory_bytes(self) + extra

    def _tail_leaves(self) -> dict:
        leaves = {"tail_live_bits": _pack_mask(self._tail_live)}
        for j in range(self.index.n_shards):
            leaves[f"shard{j}/tail_vecs"] = self._tail_vecs[j].copy()
            leaves[f"shard{j}/tail_ids"] = self._tail_ids[j].copy()
        return leaves

    def _restore_tail_leaves(self, state: dict) -> None:
        S = self.index.n_shards
        self._tail_vecs = np.stack(
            [np.asarray(state[f"shard{j}/tail_vecs"], np.float32)
             for j in range(S)])
        self._tail_ids = np.stack(
            [np.asarray(state[f"shard{j}/tail_ids"], np.int32)
             for j in range(S)])
        self._tail_live = _unpack_mask(state["tail_live_bits"],
                                       self._tail_ids.shape)
        self.tail_cap = int(self._tail_ids.shape[1])

    def to_state_dict(self) -> dict:
        st = ShardedBackend.to_state_dict(self)
        st["backend"] = self.name
        st["state_format"] = self.STATE_FORMAT
        st.update(self._mutable_leaves())
        return st

    def from_state_dict(self, state: dict) -> None:
        ShardedBackend.from_state_dict(self, state)
        if int(state.get("state_format", 1)) >= 3:
            self._restore_mutable(state)
        else:
            # a read-only sharded snapshot (v1 replicated base or v2
            # shardN/base_f): adopt it with fresh mutable state
            self._init_mutable()
