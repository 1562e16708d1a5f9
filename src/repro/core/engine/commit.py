"""Commit pipeline: batched lock-acquire, write-back, version-publish.

The begin/read/write/commit scaffolding the backends used to copy-paste
lives here as policy-agnostic steps over an engine:

  * buffered (TL2-style) commits: ``acquire_write_locks`` then
    ``write_back`` then ``release_locks`` at the new write version;
  * encounter-time (DCTL-style) commits: locks are already held, so the
    pipeline is revalidate + ``release_locks`` at the commit clock;
  * encounter-time aborts: ``rollback_inplace`` restores the undo log and
    releases the held locks at a bumped clock (the deferred-clock abort
    increment that keeps readers from missing the rollback).

Since PR 5 every step is BATCHED at write sets >= ``BULK_MIN``,
mirroring the ``read_bulk`` architecture: the lock claims become one
``ArrayLockTable.try_lock_bulk`` CAS sweep (all-or-nothing — on
conflict NOTHING was acquired, so there is no partial-hold window),
write-back and undo-restore become one heap ``scatter`` (a fancy-index
assignment on the in-place numpy heaps; the
``kernels/scatter_write.py`` Pallas kernel serves the FUNCTIONAL rows
via ``scatter_row`` — the MVStore commit's device-side block), and
lock release becomes one
``unlock_bulk`` sweep.  Below the threshold the exact historical scalar
loops run; the batch is an optimization of the common update-heavy
case, never a semantic change (``tests/test_commit_bulk.py`` pins
bulk == scalar on every backend).

LOCK-INDEX NORMALIZATION: every release path here deals in DEDUPED lock
indices, never raw heap addresses.  Two addresses can collide into one
lock word (the tables are hash-indexed), and releasing per-address
unlocks that word TWICE — after the first release another thread can
legitimately claim it, and the second release stomps their lock.
``held_write_indices`` is the single home of the address->index
normalization: the undo log's addresses through ``locks.index`` plus
the policy's explicit encounter-time index set (``d.locked_idxs`` —
irrevocable read-locks ride there), deduplicated.  ``rollback_inplace``
historically iterated ``d.write_map`` instead, which only worked
because the DCTL family happened to key it by index; the contract is
now explicit and collision-safe for any policy.

Every helper takes the engine explicitly — policies stay ~50-line
stateless-ish objects and the engine stays the single owner of heap,
clock and lock table.
"""
from __future__ import annotations

import contextlib
from typing import Iterable, List, Optional

import numpy as np

from repro.core.engine.validation import BULK_MIN
from repro.reliability import faultpoints as FP


# ---------------------------------------------------------------------------
# shared vector helpers
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def acquire_ascending(locks):
    """Hold several commit locks at once, released in reverse order.

    The caller passes the locks already sorted by a global total order
    (shard id for the sharded store) — the same ascending discipline
    ``Striped.for_indices`` uses for lock-table stripes, lifted to whole
    commit locks, so two cross-shard commits with overlapping footprints
    can never deadlock.  Unwind (including a simulated crash) releases
    whatever was acquired: lock state models hardware mutexes, which the
    fault-injection contract says still clean up.
    """
    held = []
    try:
        for lk in locks:
            lk.acquire()
            held.append(lk)
        yield
    finally:
        for lk in reversed(held):
            lk.release()


def addr_lock_indices(eng, addrs: Iterable[int]) -> np.ndarray:
    """Heap addresses -> DEDUPED ascending lock indices.

    The normalization every bulk acquire/release shares: vectorized
    through ``index_bulk`` when the lock table has it, the scalar
    ``index`` loop otherwise; ``np.unique`` collapses colliding
    addresses to one claim/release per lock word.
    """
    # materialize first: np.fromiter(..., count=len(...)) needs a sized
    # iterable, and callers legitimately pass generators
    if not hasattr(addrs, "__len__"):
        addrs = list(addrs)
    a = np.fromiter((int(x) for x in addrs), np.int64, len(addrs))
    index_bulk = getattr(eng.locks, "index_bulk", None)
    if index_bulk is not None:
        return np.unique(index_bulk(a))
    return np.unique(np.fromiter((eng.locks.index(int(x)) for x in a),
                                 np.int64, a.size))


def held_write_indices(eng, d) -> np.ndarray:
    """Every lock index this attempt's writes hold, deduplicated.

    Union of the undo log's addresses (normalized via ``locks.index``)
    and the policy's explicit encounter-time index set — DCTL's
    irrevocable mode read-locks indices that never enter the undo log,
    so both sources are needed.
    """
    idxs = set(int(i) for i in getattr(d, "locked_idxs", ()))
    if d.undo:
        idxs.update(int(i) for i in addr_lock_indices(eng, d.undo))
    return np.fromiter(sorted(idxs), np.int64, len(idxs))


def dedup_last_wins(addrs: np.ndarray, values):
    """Collapse duplicate addresses in a write batch, LAST write winning.

    ``Txn.write_bulk`` promises ``for a, v: write(a, v)`` semantics;
    buffered backends get last-write-wins for free from their dict
    update, but a heap ``scatter`` with duplicate indices keeps an
    UNSPECIFIED writer (numpy) or a nondeterministic one (jax scatter).
    The encounter-time bulk paths route through here first; the common
    duplicate-free batch pays one vectorized uniqueness check.
    """
    if np.unique(addrs).size == addrs.size:
        return addrs, values
    m = dict(zip(addrs.tolist(), list(values)))
    return np.fromiter(m.keys(), np.int64, len(m)), list(m.values())


def extend_and_relock(eng, d, idxs: np.ndarray):
    """Snapshot extension for a version-blocked bulk write claim.

    Under the deferred clock, a writer's own previous commit leaves its
    lock words at version == the CURRENT clock, so the next
    transaction's claim (which requires ``version < r_clock``) fails
    even though nothing conflicts — the scalar path eats an abort and a
    full replay for it.  TinySTM's snapshot-extension argument applies
    instead: if no word is foreign-locked or flagged and the read set
    still revalidates RIGHT NOW, the transaction can serialize at a
    later snapshot — an abort-and-replay would re-read exactly the
    values it already holds (that is what revalidation proves).  So:
    advance the snapshot past the current clock (bumping the deferred
    clock, exactly as the abort it replaces would have), revalidate,
    and retry the claim once.  Returns the newly-claimed indices or
    ``None`` (caller aborts / falls back).

    ORDER MATTERS: the clock is bumped BEFORE revalidating, and the
    revalidation runs at the OLD ``r_clock``; only on success does the
    snapshot advance to the bumped value.  Any foreign commit that
    completes after the bump publishes at >= the new snapshot and fails
    the final commit's V_LT; any foreign commit before it is caught by
    the revalidation here (its lock is still held, or its published
    version is >= the old ``r_clock``).  Revalidate-then-bump had a
    hole: a foreign commit landing entirely between the two steps
    publishes at the PRE-bump clock, which the extended snapshot then
    accepts as valid — a stale read the final revalidation can never
    catch.
    """
    ver, own, meta = eng.locks.gather(idxs)
    foreign = ((meta & 1) != 0) & (own != d.tid)
    flagged = (meta & 2) != 0
    if bool((foreign | flagged).any()):
        return None
    candidate = eng.clock.increment()
    if not eng.revalidate(d):
        return None
    d.r_clock = candidate
    return eng.locks.try_lock_bulk(idxs, d.tid, max_version=d.r_clock)


def extend_snapshot(eng, d) -> bool:
    """Scalar twin of ``extend_and_relock``'s clock step.

    The scalar encounter-time write hits the same deferred-clock
    self-conflict as the bulk claim: a writer's own previous commit left
    the lock word at version == the current clock, so ``validate``
    (``version < r_clock``) fails with nothing actually conflicting, and
    back-to-back commits eat one abort each.  The caller has already
    established that the word is neither foreign-locked nor flagged;
    this advances the snapshot and revalidates, after which the caller
    re-reads the word and retries the claim once.

    Same ordering pin as the bulk path: the clock is bumped BEFORE
    revalidating (which runs at the OLD ``r_clock``), and only on
    success does the snapshot advance — a foreign commit racing the
    extension either publishes at >= the new snapshot (caught by the
    final commit's V_LT) or is caught by the revalidation here.
    Returns True iff the snapshot advanced; False means abort.
    """
    candidate = eng.clock.increment()
    if not eng.revalidate(d):
        return False
    d.r_clock = candidate
    return True


def merge_undo(eng, d, addrs: np.ndarray) -> None:
    """Record pre-images for a write batch in one heap gather.

    First write wins: entries already in the undo log are the true
    pre-images (an earlier write in this transaction put them there), so
    the fresh gather only fills the gaps — ``merged.update(d.undo)``
    keeps every existing entry.  The encounter-time ``write_bulk``
    paths call this after their lock sweep and before their scatter.
    """
    from repro.core.engine.bulkread import heap_gather
    olds = heap_gather(eng.heap, addrs)
    if isinstance(olds, np.ndarray):
        olds = olds.tolist()
    merged = dict(zip(addrs.tolist(), olds))
    merged.update(d.undo)
    d.undo = merged


def heap_scatter(heap, addrs, values, tid: int = -1) -> None:
    """``heap[addrs] = values`` in one pass (the write-back twin of
    ``bulkread.heap_gather``).

    ``ArrayHeap`` takes a single fancy-index assignment under its lock;
    ``ObjectHeap`` takes one list pass; anything else falls back to
    scalar stores.  No kernel dispatch here: the in-place numpy heap IS
    the CPU-production representation, and gathering the whole live row
    out just to scatter the same values back would be an O(heap) round
    trip per commit — the ``scatter_write`` kernel serves the
    FUNCTIONAL rows (``scatter_row`` below, the MVStore commit's
    device-side block), which is where a TPU deployment's heap lives.

    When a fault schedule is installed the sweep splits in half around
    the ``mid_scatter`` point — a crash there leaves a PARTIAL-LANE
    heap image (half the record's lanes scattered, the rest not), the
    torn state whole-record idempotent WAL redo must heal.
    """
    sc = getattr(heap, "scatter", None)
    if sc is None:
        def sc(a, v):  # noqa: E731 - scalar-store fallback
            for ai, vi in zip(a, v):
                heap[int(ai)] = vi
    n = len(values) if hasattr(values, "__len__") else 0
    if FP.ACTIVE is not None and n > 1:
        h = n // 2
        sc(addrs[:h], values[:h])
        FP.fire("mid_scatter", tid)
        sc(addrs[h:], values[h:])
        return
    sc(addrs, values)


def scatter_row(row, addrs, values):
    """Functional ``row.at[addrs].set(values)`` with the kernel dispatch.

    The write-back analogue of ``bulkread.gather_row`` for immutable
    (jax) rows: one DONATED ``ops.publish_row`` call — a
    ``scatter_write`` launch on TPU, the jitted
    jnp scatter otherwise — so the row never round-trips through the
    host (``write_back`` returns an ndarray, a device->host heap copy
    per commit, which the device path must not pay).  The caller hands
    over ownership of ``row`` (donation invalidates it on backends
    that honor it; readers needing the old row must alias it first).
    Enforces the shared bounds contract (``check_addr_bounds``), where
    jax scatter would silently DROP an out-of-range address and wrap a
    negative one, and keeps the ``write_back`` int64-range guard:
    beyond-int32 payloads route to the exact numpy twin.  Serves the
    MVStore commit's live-block update.
    """
    from repro.core.engine.arrayheap import check_addr_bounds
    from repro.kernels import ops
    a = np.asarray(addrs, np.int64)
    check_addr_bounds(a, row.shape[0])
    vals = np.asarray(values)
    lo, hi = -(1 << 31) + 1, (1 << 31) - 1
    if vals.dtype == np.int64 and vals.size and \
            (int(vals.max()) > hi or int(vals.min()) < lo):
        import jax.numpy as jnp
        return jnp.asarray(ops.write_back(row, a, vals), row.dtype)
    return ops.publish_row(row, a, vals)


# ---------------------------------------------------------------------------
# durable commit log hooks (reliability/wal.py)
# ---------------------------------------------------------------------------
#
# Protocol (the append-before-claim invariant): a PREPARE frame carrying
# the full redo image is buffered-appended BEFORE the claim/scatter
# phase; the fsync'd DECIDE marker lands at the exact instant
# ``publish_started`` flips True, before the first heap mutation — file
# appends are sequential, so the one DECIDE fsync also makes the
# PREPARE durable.  An abandoned prepare (abort, or crash before
# DECIDE) is never replayed: rollback is free.


def wal_log_prepare(eng, d) -> None:
    """Buffered PREPARE from the buffered write map (before the claim)."""
    wal = eng.wal
    if wal is None or not d.write_map:
        return
    wm = d.write_map
    d.wal_lsn = wal.append_prepare(
        d.tid, np.fromiter(wm.keys(), np.int64, len(wm)),
        list(wm.values()), clocks=(eng.clock.load(),))


def wal_log_decide(eng, d) -> None:
    """fsync'd DECIDE at the publish_started flip (buffered path)."""
    wal = eng.wal
    if wal is None or d.wal_lsn is None:
        return
    wal.append_decide(d.wal_lsn)


def wal_log_decide_encounter(eng, d) -> None:
    """PREPARE + DECIDE for encounter-time policies, at their decide
    point (revalidation passed, locks still held).

    In-place backends scattered their values during execution, so the
    redo image is gathered FROM THE HEAP at the undo log's addresses —
    the locks guarantee those words still hold this transaction's
    values.  There is no earlier correct hook: before revalidation the
    commit may still abort (and the undo restore would un-publish the
    prepared image), so prepare and decide collapse into one append +
    one fsync here.
    """
    wal = eng.wal
    if wal is None or not d.undo:
        return
    addrs = np.fromiter(d.undo.keys(), np.int64, len(d.undo))
    vals = eng.heap.gather(addrs)
    d.wal_lsn = wal.append_prepare(
        d.tid, addrs, vals, clocks=(eng.clock.load(),))
    wal.append_decide(d.wal_lsn)


# ---------------------------------------------------------------------------
# pipeline steps
# ---------------------------------------------------------------------------


def acquire_write_locks(eng, d,
                        bulk_min: Optional[int] = None) -> List[int]:
    """Claim every buffered write's lock (commit-time locking).

    On conflict, aborts the transaction with no locks held: the scalar
    loop releases whatever it had acquired (versions untouched); the
    bulk sweep (write sets >= ``bulk_min``, default ``BULK_MIN``) is
    all-or-nothing and never acquired in the first place.  Returns the
    locked indices, deduplicated (ascending on the bulk path,
    acquisition order on the scalar path).
    """
    bm = BULK_MIN if bulk_min is None else bulk_min
    wal_log_prepare(eng, d)
    if FP.ACTIVE is not None:
        FP.fire("pre_claim", d.tid)
    try_bulk = getattr(eng.locks, "try_lock_bulk", None)
    if try_bulk is not None and len(d.write_map) >= bm:
        claimed = try_bulk(addr_lock_indices(eng, d.write_map), d.tid)
        if claimed is None:
            eng.abort_txn(d)
        locked = claimed.tolist()
    else:
        locked: List[int] = []
        for addr in d.write_map:
            idx = eng.locks.index(addr)
            st = eng.locks.read(idx)
            if not eng.locks.try_lock(idx, st, d.tid):
                release_locks(eng, locked)
                eng.abort_txn(d)
            if idx not in locked:
                locked.append(idx)
    if FP.ACTIVE is not None:
        try:
            FP.fire("post_claim", d.tid)
        except BaseException as e:
            # an injected recoverable error must not leak the claim the
            # caller never saw; a simulated crash must leave it held
            if not FP.is_simulated_crash(e):
                release_locks(eng, locked)
            raise
    return locked


def write_back(eng, d, bulk_min: Optional[int] = None) -> None:
    """Publish buffered writes to the heap (caller holds the locks).

    One heap ``scatter`` at write sets >= ``bulk_min`` (write maps are
    dict-keyed, so the addresses are unique — the scatter contract);
    the scalar store loop below it.
    """
    bm = BULK_MIN if bulk_min is None else bulk_min
    wm = d.write_map
    if FP.ACTIVE is not None:
        FP.fire("pre_scatter", d.tid)
    if d.wal_lsn is None:
        # policy skipped acquire_write_locks (or the WAL was attached
        # mid-operation): prepare here so the decide below has a frame
        wal_log_prepare(eng, d)
    # commit record: from here the decision is publish — a crash below
    # rolls FORWARD from write_map (recovery.recover_engine), and the
    # durable DECIDE marker lands BEFORE the first heap mutation
    wal_log_decide(eng, d)
    d.publish_started = True
    if len(wm) >= bm and getattr(eng.heap, "scatter", None) is not None:
        addrs = np.fromiter(wm.keys(), np.int64, len(wm))
        heap_scatter(eng.heap, addrs, list(wm.values()), tid=d.tid)
        if FP.ACTIVE is not None:
            FP.fire("post_scatter", d.tid)
        return
    if FP.ACTIVE is not None and len(wm) > 1:
        # same partial-lane split as heap_scatter, for the scalar path
        items = list(wm.items())
        h = len(items) // 2
        for addr, value in items[:h]:
            eng.heap[addr] = value
        FP.fire("mid_scatter", d.tid)
        for addr, value in items[h:]:
            eng.heap[addr] = value
        FP.fire("post_scatter", d.tid)
        return
    for addr, value in wm.items():
        eng.heap[addr] = value
    if FP.ACTIVE is not None:
        FP.fire("post_scatter", d.tid)


def release_locks(eng, idxs: Iterable[int],
                  version: Optional[int] = None,
                  bulk_min: Optional[int] = None) -> None:
    """Release lock INDICES (never raw addresses), optionally publishing
    ``version``; one ``unlock_bulk`` sweep at batches >= ``bulk_min``."""
    bm = BULK_MIN if bulk_min is None else bulk_min
    arr = idxs if isinstance(idxs, np.ndarray) else None
    n = arr.size if arr is not None else len(idxs)  # type: ignore[arg-type]
    unlock_bulk = getattr(eng.locks, "unlock_bulk", None)
    if unlock_bulk is not None and n >= bm:
        if arr is None:
            # no int() per element: callers pass int/np-int indices and
            # fromiter's dtype cast covers both at C speed
            arr = np.fromiter(idxs, np.int64, n)
        unlock_bulk(arr, version)
        return
    for idx in idxs:
        eng.locks.unlock(int(idx), version)


def rollback_inplace(eng, d, bump_clock: bool = True,
                     bulk_min: Optional[int] = None) -> None:
    """Undo encounter-time in-place writes and release the held locks.

    ``bump_clock`` implements the deferred clock's abort increment: the
    released locks are republished at a FRESH version so any reader that
    validated against the uncommitted value must revalidate and abort.
    The undo restore is one heap ``scatter`` at >= ``bulk_min`` entries,
    and the release set is ``held_write_indices`` — deduped lock
    indices, never per-address unlocks (see the module docstring's
    normalization note).
    """
    bm = BULK_MIN if bulk_min is None else bulk_min
    undo = d.undo
    if len(undo) >= bm and getattr(eng.heap, "scatter", None) is not None:
        addrs = np.fromiter(undo.keys(), np.int64, len(undo))
        heap_scatter(eng.heap, addrs, list(undo.values()), tid=d.tid)
    else:
        for addr, old in undo.items():
            eng.heap[addr] = old
    nxt = eng.clock.increment() if bump_clock else None
    release_locks(eng, held_write_indices(eng, d), nxt, bulk_min=bm)
