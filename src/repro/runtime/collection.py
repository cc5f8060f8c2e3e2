"""Sharded collection resource: ONE logical repository, pod-scale placement,
epoch-versioned live updates, crash-consistent snapshots.

Every serving layer built before this module sharded *queries*; the
collection itself (CSR inverted index triplet, embedding table, set-norm
metadata) lived whole on one device inside each ``KoiosSearch``.  This
module makes the collection a first-class **resource object**:

:class:`ShardedCollection`
    Owns the repository split — contiguous set ranges over a shard axis
    (paper §VI; LES3 makes the same partition-level-index argument for
    exact set search at corpus scale) — and ALL of its device state.
    Built once, shared by every consumer: ``KoiosSearch`` instances, the
    request engine, engine replicas behind the admission router, and
    benchmarks all borrow the same per-shard operand views, so the CSR
    triplet / dense token matrix / normalized embedding table of a shard
    is uploaded exactly once per process, not once per consumer.

:class:`Shard`
    One contiguous set range: the partition-local :class:`SetCollection`,
    its inverted index, the global id offset, and an optional *placement
    device*.  The search/scheduler/wave layers receive Shards wherever
    they historically received ``KoiosIndex``es (``Shard`` IS a
    ``KoiosIndex`` — same host fields, so the host pipeline is oblivious)
    and **borrow** device operands through the accessors below instead of
    owning uploads:

      ``csr_arrays()``    int32 CSR triplet for in-trace event expansion
      ``wave_operands()`` dense (num_sets, c_pad) token matrix + sizes
      ``table_for(sim)``  the provider's row-normalized embedding table,
                          resident on the shard's device

Placement: ``ShardedCollection.build(..., devices=...)`` pins shard *i*'s
arrays to device *i* (``jax.device_put``); each shard's fused wave then
runs where its data lives, and the theta_lb carry hops device-to-device
between waves (the shared-bound exchange of DESIGN.md §5 — the same
``all_reduce_max`` contract, realised as carry chaining when waves are
driven from one host).  ``devices=None`` leaves every array uncommitted
on the default device — the single-device case is the degenerate 1-place
instance of the same code path, not a fork.

Live updates (DESIGN.md §6.5): the repository is no longer
process-lifetime-immutable.  A :class:`CollectionUpdate` transaction
(``begin_update() / add_sets() / remove_sets() / commit()``) produces a
new :class:`CollectionEpoch` by **copy-on-write over shards**: only
shards whose membership changed rebuild their local collection /
inverted index (and therefore their CSR / operand / table device state);
unchanged shards are re-wrapped sharing the same ``coll``/``inv`` and
the same cached device arrays by reference (a shard's device state
depends only on its LOCAL content, never on its global offset — offsets
are applied host-side when tiles finish).  Readers (``ExecutionPlan``s,
engines) ``pin()`` the epoch they were admitted under and stay bit-exact
against that consistent snapshot; ``release()`` of the last reader of a
non-head epoch drops the device state exclusive to it (the reader-drain
rule: an old epoch's buffers are only released after its readers drain).

Crash consistency: ``save()/restore()`` write per-shard payloads plus an
epoch manifest through the ``checkpoint/`` machinery with
write-temp-then-atomic-rename — a crash mid-commit restores either the
old or the new epoch, never a torn mix
(:class:`repro.checkpoint.collection.CollectionSnapshotter`).

Exactness is placement-, shard-count-, and epoch-invariant: shard
boundaries only change which tile a set's events land in, every per-set
numeric is computed from shard-local operands identical to the unsharded
slices, and the shared theta_lb bound is only ever raised (monotone,
certified) — so sharded top-k is bit-identical to the 1-shard reference
(tests/test_sharded_collection.py), and a pinned epoch's top-k is
bit-identical to a fresh build of that epoch's repository
(tests/test_collection_epoch.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core.inverted_index import InvertedIndex
from ..core.search import KoiosIndex, partition_ranges
from ..core.types import SetCollection, assert_int32, lane_cover
from . import instrument


class UpdateValidationError(ValueError):
    """A live-update transaction carried invalid sets or set ids —
    raised at ``add_sets``/``remove_sets``/``commit`` time so a bad
    update can never corrupt the committed repository."""


@dataclasses.dataclass
class Shard(KoiosIndex):
    """One contiguous set range of the repository + its device residency.

    Host fields are exactly ``KoiosIndex`` (coll, inv, id_offset), so the
    scheduler's tiles and the host pipeline consume Shards unchanged.
    Device state is built lazily on first borrow and cached on the shard
    — the ShardedCollection (not any search object) is its owner, and its
    lifetime is the owning epoch's lifetime (reader-drain rule).
    """

    sid: int = 0                     # shard index within the collection
    device: Optional[Any] = None     # placement; None = default device

    def _put(self, x):
        """Upload ``x`` honoring the shard's placement."""
        import jax
        import jax.numpy as jnp

        if self.device is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.device)

    # ------------------------------------------------------------ borrows
    def csr_arrays(self):
        """Device-resident int32 CSR triplet (indptr, posting_set,
        posting_slot) for the fused wave's in-trace event expansion
        (DESIGN.md §3.3) — uploaded once per shard lifetime.

        Unplaced shards delegate to ``InvertedIndex.device_arrays`` —
        ONE cache (and one ``h2d:index_upload`` record) shared with any
        direct index consumer; placed shards pin a committed copy."""
        if self.device is None:
            return self.inv.device_arrays()
        cached = self.__dict__.get("_csr")
        if cached is None:
            assert_int32(self.inv.total_postings, "total_postings")
            instrument.record(f"h2d:index_upload[s{self.sid}]")
            pad = np.zeros(1, np.int32)
            cached = tuple(self._put(a) for a in (
                self.inv.tok_indptr.astype(np.int32),
                np.concatenate(
                    [self.inv.posting_set.astype(np.int32), pad - 1]),
                np.concatenate(
                    [self.inv.posting_slot.astype(np.int32), pad])))
            self._csr = cached
        return cached

    def wave_operands(self):
        """Dense (num_sets, c_pad) token matrix + int32 set sizes + the
        column pad ``c_pad``, the cover (``lane_cover``) of the shard's
        largest set — the fused wave's verification operands, built and
        uploaded once per shard lifetime.

        On a size-skewed shard one outlier set inflates ``c_pad`` for
        every row — token-balanced sharding (``by='tokens'``) keeps
        shards uniform; at repository-shard scales the dense form is what
        keeps every round's weight gather one slice."""
        cached = self.__dict__.get("_wave_ops")
        if cached is None:
            coll = self.coll
            sizes = coll.set_sizes
            c_pad = lane_cover(int(sizes.max()) if len(sizes) else 1)
            dense = np.full((coll.num_sets, c_pad), -1, np.int32)
            if coll.total_tokens:
                rows = np.repeat(np.arange(coll.num_sets), sizes)
                cols = np.arange(coll.total_tokens) \
                    - np.repeat(coll.set_indptr[:-1], sizes)
                dense[rows, cols] = coll.set_tokens
            if self.device is not None:
                instrument.record(f"h2d:operand_upload[s{self.sid}]")
            cached = (self._put(dense), self._put(sizes.astype(np.int32)),
                      c_pad)
            self._wave_ops = cached
        return cached

    def table_for(self, sim_provider):
        """The provider's row-L2-normalized embedding table, resident on
        this shard's device.  Unplaced shards share the provider's own
        cached device table (one upload per provider, process-wide);
        placed shards keep one pinned copy per (provider, device)."""
        from ..core.similarity import normalized_table_for

        table = normalized_table_for(sim_provider)
        if self.device is None:
            return table
        cache = self.__dict__.setdefault("_tables", {})
        hit = cache.get(id(sim_provider))
        if hit is None:
            import jax

            instrument.record(f"h2d:table_upload[s{self.sid}]")
            # pin the provider so its id cannot be recycled while cached
            hit = cache[id(sim_provider)] = (
                jax.device_put(table, self.device), sim_provider)
        return hit[0]

    # ------------------------------------------------------ copy-on-write
    def share_as(self, id_offset: int, sid: int) -> "Shard":
        """A new Shard over the SAME local collection/index (and the same
        cached device arrays, by reference) at a possibly different
        global offset — the copy-on-write share of an unchanged shard
        across a commit.  Sound because every device operand is a pure
        function of the LOCAL collection: the global offset is added
        host-side when a tile's partition-local top-k is finished, so two
        epochs can disagree about a shard's offset while sharing every
        one of its buffers."""
        s = Shard(coll=self.coll, inv=self.inv,
                  id_offset=int(id_offset), sid=int(sid),
                  device=self.device)
        for k in ("_csr", "_wave_ops", "_tables"):
            if k in self.__dict__:
                s.__dict__[k] = self.__dict__[k]
        return s

    def drop_device_state(self) -> None:
        """Release this shard's cached device arrays (reader-drain of a
        retired epoch).  The JAX buffers free when the last Python
        reference dies — shards of live epochs sharing the same ``inv``
        keep theirs (the owner checks liveness before calling)."""
        self.__dict__.pop("_csr", None)
        self.__dict__.pop("_wave_ops", None)
        self.__dict__.pop("_tables", None)
        if self.inv is not None:
            self.inv.__dict__.pop("_device_arrays", None)


# --------------------------------------------------------------- helpers
def _coll_from_sets(token_sets: Sequence[np.ndarray],
                    vocab_size: int) -> SetCollection:
    """A CSR :class:`SetCollection` from a list of per-set token arrays."""
    sizes = np.asarray([len(t) for t in token_sets], np.int64)
    indptr = np.zeros(len(token_sets) + 1, np.int64)
    np.cumsum(sizes, out=indptr[1:])
    tokens = (np.concatenate([np.asarray(t, np.int32) for t in token_sets])
              if token_sets else np.zeros(0, np.int32))
    return SetCollection(set_indptr=indptr, set_tokens=tokens,
                         vocab_size=int(vocab_size))


def _concat_colls(colls: Sequence[SetCollection],
                  vocab_size: int) -> SetCollection:
    """Concatenate shard-local collections back into one repository."""
    indptr = [np.zeros(1, np.int64)]
    tokens = []
    base = 0
    for c in colls:
        indptr.append(c.set_indptr[1:] + base)
        tokens.append(c.set_tokens)
        base += c.total_tokens
    return SetCollection(
        set_indptr=np.concatenate(indptr),
        set_tokens=(np.concatenate(tokens) if tokens
                    else np.zeros(0, np.int32)),
        vocab_size=int(vocab_size))


@dataclasses.dataclass
class CollectionEpoch:
    """One immutable version of the repository: the global collection,
    its shard list, and a reader refcount.  Readers (engines, one-shot
    plans) ``pin()`` the epoch they execute against — their top-k is
    computed from this consistent snapshot bit-exactly, however many
    commits land while they run — and ``release()`` it when done; the
    last release of a non-head epoch drops its exclusive device state
    (the reader-drain rule, DESIGN.md §6.5)."""

    epoch: int
    coll: SetCollection
    shards: List[Shard]
    readers: int = 0


class CollectionUpdate:
    """One open live-update transaction against the head epoch.

    ``add_sets``/``remove_sets`` stage changes; ``commit`` builds the
    next :class:`CollectionEpoch` copy-on-write (only shards whose
    membership changed rebuild — additions append to the LAST shard,
    removals rebuild their owning shard; everything else is shared by
    reference) and installs it as head.  Ids in ``remove_sets`` are
    global set ids of the epoch the transaction was opened against; a
    commit defines the NEXT epoch's id space (contiguous CSR — removals
    shift later ids down, additions append at the end).  One transaction
    may be open at a time; ``abort()`` discards it."""

    def __init__(self, parent: "ShardedCollection"):
        self._parent = parent
        self._base = parent._head
        self._adds: List[np.ndarray] = []
        self._removes: "set[int]" = set()
        self._closed = False

    def _check_open(self) -> None:
        if self._closed:
            raise UpdateValidationError("update transaction already closed")
        if self._parent._head is not self._base:
            raise UpdateValidationError(
                "update transaction raced a commit (stale base epoch)")

    def add_sets(self, token_sets: Sequence[np.ndarray]) -> None:
        """Stage new sets (validated: 1-D, non-empty, distinct in-vocab
        tokens — sets, not bags, exactly like ``SetCollection``)."""
        self._check_open()
        vocab = self._base.coll.vocab_size
        for t in token_sets:
            a = np.asarray(t)
            if a.ndim != 1 or a.size == 0:
                raise UpdateValidationError(
                    f"added set must be a non-empty 1-D token array, "
                    f"got shape {a.shape}")
            if not np.issubdtype(a.dtype, np.integer):
                raise UpdateValidationError(
                    f"added set has non-integer dtype {a.dtype}")
            if a.min() < 0 or a.max() >= vocab:
                raise UpdateValidationError(
                    f"added set tokens outside [0, {vocab})")
            if len(np.unique(a)) != len(a):
                raise UpdateValidationError(
                    "added set contains duplicate tokens (sets, not bags)")
            self._adds.append(a.astype(np.int32).copy())

    def remove_sets(self, set_ids: Sequence[int]) -> None:
        """Stage removals by GLOBAL set id of the transaction's base
        epoch."""
        self._check_open()
        n = self._base.coll.num_sets
        for i in set_ids:
            i = int(i)
            if not 0 <= i < n:
                raise UpdateValidationError(
                    f"remove_sets id {i} outside [0, {n})")
            self._removes.add(i)

    def abort(self) -> None:
        self._closed = True
        if self._parent._update is self:
            self._parent._update = None

    def commit(self) -> int:
        """Build + install the next epoch; returns its epoch number.
        A no-op transaction (nothing staged) keeps the current epoch."""
        self._check_open()
        self._closed = True
        self._parent._update = None
        if not self._adds and not self._removes:
            return self._base.epoch

        head = self._base
        vocab = head.coll.vocab_size
        # removals grouped per owning shard (contiguous global ranges)
        rem_by_shard: Dict[int, List[int]] = {}
        for gid in self._removes:
            for si, s in enumerate(head.shards):
                lo = s.id_offset
                if lo <= gid < lo + s.coll.num_sets:
                    rem_by_shard.setdefault(si, []).append(gid - lo)
                    break
        last = len(head.shards) - 1
        new_shards: List[Shard] = []
        offset = shared = rebuilt = 0
        for si, s in enumerate(head.shards):
            local_rem = rem_by_shard.get(si, [])
            local_add = self._adds if si == last else []
            if not local_rem and not local_add:
                # membership unchanged: share coll/inv/device state by
                # reference; only the global offset may shift
                new_shards.append(s.share_as(offset, len(new_shards)))
                instrument.record(f"collection:shard_shared[s{s.sid}]")
                shared += 1
                offset += s.coll.num_sets
                continue
            keep = np.ones(s.coll.num_sets, bool)
            keep[np.asarray(local_rem, np.int64)] = False
            token_sets = [s.coll.get_set(i).copy()
                          for i in np.nonzero(keep)[0]] + local_add
            if not token_sets:
                continue                     # shard emptied out: dropped
            ncoll = _coll_from_sets(token_sets, vocab)
            new_shards.append(Shard(
                coll=ncoll, inv=InvertedIndex.build(ncoll),
                id_offset=offset, sid=len(new_shards), device=s.device))
            instrument.record(f"collection:shard_rebuilt[s{s.sid}]")
            rebuilt += 1
            offset += ncoll.num_sets
        if not new_shards:
            raise UpdateValidationError(
                "commit would empty the repository (every set removed)")
        new_coll = _concat_colls([s.coll for s in new_shards], vocab)
        ep = CollectionEpoch(epoch=head.epoch + 1, coll=new_coll,
                             shards=new_shards)
        self._parent._install(ep, shared=shared, rebuilt=rebuilt,
                              added=len(self._adds),
                              removed=len(self._removes))
        return ep.epoch


class ShardedCollection:
    """The repository as a shared, epoch-versioned resource: shards +
    their device state + the live-update/snapshot lifecycle.

    Consumers (``KoiosSearch``, ``RequestEngine``, engine replicas behind
    the :class:`~repro.runtime.engine.AdmissionRouter`) hold a reference
    and borrow operand views; none of them owns uploads.  Building the
    resource is host-only — device arrays materialize on first borrow.
    ``coll``/``shards`` always reflect the HEAD epoch; readers that need
    a consistent snapshot across steps ``pin()`` it (see
    :class:`CollectionEpoch`).
    """

    def __init__(self, coll: SetCollection, shards: Sequence[Shard],
                 epoch: int = 0):
        head = CollectionEpoch(epoch=int(epoch), coll=coll,
                               shards=list(shards))
        self._head = head
        self._retained: Dict[int, CollectionEpoch] = {head.epoch: head}
        self._update: Optional[CollectionUpdate] = None
        self._on_commit: List[Callable[["ShardedCollection"], None]] = []
        self._last_commit: Optional[dict] = None

    # --------------------------------------------------- head delegation
    @property
    def coll(self) -> SetCollection:
        return self._head.coll

    @property
    def shards(self) -> List[Shard]:
        return self._head.shards

    @property
    def epoch(self) -> int:
        return self._head.epoch

    @property
    def head(self) -> CollectionEpoch:
        return self._head

    # ---------------------------------------------------------- factories
    @staticmethod
    def build(coll: SetCollection, shards: int = 1, by: str = "sets",
              devices=None) -> "ShardedCollection":
        """Split ``coll`` into ``shards`` contiguous set ranges
        (``by='sets'`` equal counts / ``by='tokens'`` greedy token
        balance — :func:`repro.core.search.partition_ranges`) and wrap
        each in a :class:`Shard`.

        ``devices``: ``None`` keeps every shard on the default device
        (the degenerate single-place case); ``'auto'`` spreads shards
        round-robin over ``jax.devices()``; an explicit device sequence
        pins shard *i* to ``devices[i % len(devices)]``.  Empty ranges
        (``shards > num_sets``) are dropped, so every shard is
        non-empty."""
        if devices == "auto":
            import jax

            devices = jax.devices()
        bounds = partition_ranges(coll.set_sizes, shards, by=by)
        out: List[Shard] = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            if hi <= lo:
                continue
            sid = len(out)
            dev = devices[sid % len(devices)] if devices else None
            out.append(Shard(
                coll=coll.slice_sets(int(lo), int(hi)),
                inv=None, id_offset=int(lo), sid=sid, device=dev))
        for s in out:
            s.inv = InvertedIndex.build(s.coll)
        return ShardedCollection(coll, out)

    @staticmethod
    def adopt(coll: SetCollection,
              indexes: Sequence[KoiosIndex]) -> "ShardedCollection":
        """Wrap prebuilt partition indexes (or existing Shards) as a
        collection resource — the compatibility entry for callers that
        built ``KoiosIndex``es directly.  Existing Shards keep their
        cached device state (and sid/placement)."""
        shards = [ix if isinstance(ix, Shard)
                  else Shard(coll=ix.coll, inv=ix.inv,
                             id_offset=ix.id_offset, sid=sid)
                  for sid, ix in enumerate(indexes)]
        return ShardedCollection(coll, shards)

    # --------------------------------------------------- epoch lifecycle
    def pin(self) -> CollectionEpoch:
        """Take a reader reference on the head epoch.  The returned
        epoch's ``coll``/``shards`` stay valid (device state retained)
        until the matching :meth:`release` — however many commits land
        meanwhile."""
        self._head.readers += 1
        return self._head

    def release(self, ep: CollectionEpoch) -> None:
        """Drop a reader reference.  The LAST reader of a retired
        (non-head) epoch releases the device state exclusive to it —
        never state shared with a live epoch (COW shards keep their
        buffers through the epochs that still reference them)."""
        ep.readers = max(ep.readers - 1, 0)
        if ep.readers == 0 and ep is not self._head:
            self._retained.pop(ep.epoch, None)
            self._release_device_state(ep)

    def _release_device_state(self, ep: CollectionEpoch) -> None:
        live = {id(s.inv) for e in self._retained.values()
                for s in e.shards}
        for s in ep.shards:
            if id(s.inv) in live:
                continue
            s.drop_device_state()
            instrument.record(f"collection:epoch_release[s{s.sid}]")

    def begin_update(self) -> CollectionUpdate:
        """Open the (single) live-update transaction against the head
        epoch."""
        if self._update is not None:
            raise UpdateValidationError(
                "an update transaction is already open")
        self._update = CollectionUpdate(self)
        return self._update

    def on_commit(self,
                  callback: Callable[["ShardedCollection"], None]) -> None:
        """Register a post-commit hook (fired after the new epoch is
        installed as head — ``serve.py --snapshot-dir`` snapshots here)."""
        self._on_commit.append(callback)

    def _install(self, ep: CollectionEpoch, shared: int, rebuilt: int,
                 added: int, removed: int) -> None:
        old = self._head
        self._retained[ep.epoch] = ep
        self._head = ep
        self._last_commit = {"epoch": ep.epoch, "shards_shared": shared,
                             "shards_rebuilt": rebuilt,
                             "sets_added": added, "sets_removed": removed}
        instrument.record("collection:commit")
        if old.readers == 0:
            self._retained.pop(old.epoch, None)
            self._release_device_state(old)
        for cb in self._on_commit:
            cb(self)

    # ------------------------------------------------- crash consistency
    def save(self, directory: str) -> dict:
        """Snapshot the HEAD epoch into ``directory`` (per-shard payloads
        + atomic epoch manifest: old-or-new, never torn).  Returns the
        manifest written."""
        from ..checkpoint.collection import CollectionSnapshotter

        return CollectionSnapshotter(directory).save(self)

    @staticmethod
    def restore(directory: str,
                devices=None) -> "Optional[ShardedCollection]":
        """Rebuild the collection (same shard split, same epoch number)
        from the latest manifest in ``directory``; ``None`` when no
        snapshot exists.  ``devices`` re-places shards exactly as
        :meth:`build` would (placement is host policy, not snapshot
        state)."""
        from ..checkpoint.collection import CollectionSnapshotter

        return CollectionSnapshotter(directory).restore(devices=devices)

    # ----------------------------------------------------------- geometry
    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def placed(self) -> bool:
        """Whether any shard is pinned to an explicit device."""
        return any(s.device is not None for s in self.shards)

    def shard_ranges(self) -> List[tuple]:
        """[(lo, hi)) global set-id range per shard."""
        return [(s.id_offset, s.id_offset + s.coll.num_sets)
                for s in self.shards]

    def device_bytes(self) -> int:
        """Host-side estimate of the per-shard device footprint already
        materialized (CSR triplets + dense operand matrices), over every
        RETAINED epoch's distinct shards (COW shares count once)."""
        total = 0
        seen = set()
        for e in self._retained.values():
            for s in e.shards:
                if id(s.inv) in seen:
                    continue
                seen.add(id(s.inv))
                if s.__dict__.get("_csr") is not None:
                    total += (4 * (s.inv.vocab_size + 1)
                              + 2 * 4 * (s.inv.total_postings + 1))
                ops = s.__dict__.get("_wave_ops")
                if ops is not None:
                    total += 4 * s.coll.num_sets * (ops[2] + 1)
        return total

    def describe(self) -> dict:
        """Placement/footprint/epoch summary (serving observability)."""
        return {
            "num_sets": self.coll.num_sets,
            "epoch": self.epoch,
            "retained_epochs": sorted(self._retained),
            "pinned_readers": {e: ep.readers
                               for e, ep in sorted(self._retained.items())
                               if ep.readers},
            "last_commit": self._last_commit,
            "shards": [
                {"sid": s.sid, "sets": s.coll.num_sets,
                 "tokens": s.coll.total_tokens,
                 "device": str(s.device) if s.device is not None else None}
                for s in self.shards],
            "device_bytes": self.device_bytes(),
        }
