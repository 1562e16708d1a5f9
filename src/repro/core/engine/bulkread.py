"""Batched transactional reads (`Txn.read_bulk`) for lock-version policies.

The paper's long-running read-only transactions scan thousands of words;
word-at-a-time through Python, the scan measures the interpreter rather
than the TM.  This module is the engine-level batch: ONE heap gather
bracketed by TWO consistent lock-word gathers, then a vectorized
stability predicate — so a long read snapshots its whole batch in a
handful of array ops (numpy on CPU, the ``kernels/gather_read.py`` /
``kernels/validate.py`` Pallas launches on TPU — ``kernels.ops.on_tpu``).

Soundness argument, per element ``i``:

  * ``pre``/``post`` are consistent (locked, version, tid, flag) tuples —
    the lock table packs each word into one int64, gathered in one
    fancy-index (``ArrayLockTable.gather``), so no field tearing;
  * if ``pre.version == post.version``, both unlocked and unflagged, the
    heap word cannot have been mutated between the two gathers: every
    writer in the lock-version family locks the word before touching data
    and republishes a bumped version on release;
  * ``version <(=) r_clock`` then places the stable value at/before the
    transaction's snapshot — exactly the scalar read's validation, so an
    accepted element is indistinguishable from a scalar read of the same
    address at the same point.

Elements that FAIL the predicate (locked, flagged, version too new, or
torn between the gathers) are NOT errors: the caller re-reads just those
through the policy's scalar path, which spins/extends/aborts with the
policy's exact semantics.  The batch is an optimization of the common
case (a quiescent majority), never a semantic change.

Multiverse's VERSIONED readers (paper SS3.1/SS4.2) add a vectorized
middle tier between the batch and the scalar walk: the failed elements
are precisely the recently-written words a versioned reader serves from
version lists, and the packed VLT mirror (``core/vlt.py`` —
per-lock-index int64 rows of the newest committed ``(timestamp, data)``
pairs, seqlock-bracketed) resolves them in ONE ``PackedVLT.select``
gather — ``np_version_select`` on CPU, the
``kernels/version_select.py`` Pallas kernel on TPU
— so the Mode-U/Q hybrid bulk read (``MultiversePolicy.read_bulk`` →
``_bulk_versioned_gather``) only falls through to the per-word
version-list traversal for what the mirror cannot represent (colliding
buckets, non-int payloads, torn rows, versions deeper than the mirror).

Own writes: encounter-time policies (DCTL/TinySTM/Multiverse) see their
in-place values in the heap gather already, but those addresses skip
validation and the read set (the scalar paths return them early);
buffered-write policies (TL2/NOrec) overlay ``write_map`` on the result.
"""
from __future__ import annotations

from typing import Any, Sequence

import numpy as np

from repro.runtime.tracing import span

__all__ = ["as_addr_array", "bulk_read_lockver", "finish_with_scalar",
           "gather_row", "heap_gather", "shard_partition"]


def shard_partition(shard_ids: np.ndarray, n_shards: int):
    """Group a routed address batch by shard: ``[(sid, positions)]``.

    ``shard_ids[i]`` is the shard owning batch element ``i``
    (``0 <= sid < n_shards``).  Returns one entry per shard actually
    present, ``positions`` ascending (stable sort), so the caller runs
    ONE gather/scatter per shard and reassembles order-preserving with
    ``out[positions] = shard_vals`` — the routing layer between a
    cross-shard bulk op and the per-shard kernel launches.
    """
    sid = np.asarray(shard_ids, np.int64)
    order = np.argsort(sid, kind="stable")
    bounds = np.searchsorted(sid[order], np.arange(n_shards + 1))
    return [(s, order[bounds[s]:bounds[s + 1]])
            for s in range(n_shards) if bounds[s] < bounds[s + 1]]


def as_addr_array(addrs: Sequence[int]) -> np.ndarray:
    """Normalize any address batch (range, list, ndarray) to int64[N]."""
    if isinstance(addrs, np.ndarray):
        return addrs.astype(np.int64, copy=False)
    if isinstance(addrs, range):
        return np.arange(addrs.start, addrs.stop, addrs.step, np.int64)
    return np.fromiter((int(a) for a in addrs), np.int64)


def gather_row(row, addrs: np.ndarray) -> np.ndarray:
    """``row[addrs]`` with the kernel dispatch, for any 1-D value row.

    Fancy-index on CPU; one ``ops.snapshot_read`` (gather_read kernel)
    launch on TPU.  The single home of the bounds contract on the kernel
    path: numpy raises on an out-of-range address while a device gather
    would read whatever row the DMA addresses, so the guard keeps both
    paths raising identically.  Serves the word-level array heap AND the
    MVStore live-block / ring-row gathers.
    """
    from repro.kernels import ops
    if ops.on_tpu():
        from repro.core.engine.arrayheap import check_addr_bounds
        check_addr_bounds(addrs, row.shape[0])
        return ops.snapshot_read(row, addrs)
    if isinstance(row, np.ndarray):
        return row[addrs]
    if hasattr(row, "shape"):
        # device-resident (jax) row: gather ON DEVICE and materialize
        # only the batch — ``np.asarray(row)[addrs]`` would host-copy
        # the whole row per call.  jnp fancy-indexing CLAMPS instead of
        # raising, so the bounds contract needs the explicit guard.
        if addrs.size and (int(addrs.max(initial=0)) >= row.shape[0]
                           or int(addrs.min(initial=0)) < 0):
            raise IndexError(int(addrs.max()))
        return np.asarray(row[addrs])
    return np.asarray(row)[addrs]


def heap_gather(heap, addrs: np.ndarray):
    """``heap[addrs]`` in one pass.

    ``ArrayHeap`` answers with a single fancy-index (one ``gather_row``
    kernel launch over ``heap.jnp()`` on TPU — unless the heap holds a
    word beyond int32, which the device copy would truncate: that heap
    takes the numpy twin, counted in ``ops.COUNTS``); ``ObjectHeap``
    with one list pass; anything else falls back to scalar indexing.
    Returns ndarray (array heaps) or list (object heaps).
    """
    g = getattr(heap, "gather", None)
    if g is None:
        return [heap[int(a)] for a in addrs]
    if getattr(heap, "jnp", None) is not None:
        from repro.kernels import ops
        if ops.on_tpu():
            if heap.fits_int32:
                with span("tm.read_bulk.heap_upload") as sp:
                    row = heap.jnp()
                    ops.COUNTS.moved("heap_upload", h2d=row.nbytes)
                    sp.set(h2d_bytes=row.nbytes)
                return gather_row(row, addrs)
            ops.COUNTS.twin("gather_read")
    return g(addrs)


def bulk_read_lockver(eng, d, addrs: np.ndarray, *, inclusive: bool,
                      track: bool = True):
    """One batched read attempt against the lock-version protocol.

    ``inclusive`` selects the version predicate for NEW reads:
    ``version <= r_clock`` (TL2/TinySTM-style clocks, bumped on commit
    only) vs strict ``<`` (the Multiverse/DCTL deferred clock, where the
    commit in flight at ``r_clock`` may still be publishing).  ``track``
    appends accepted entries to ``d.read_set`` for commit-time
    revalidation — versioned Multiverse readers pass ``track=False``
    (they read the past; there is nothing to revalidate at commit).

    Returns ``(values, ok)``: ``values`` is the gathered batch (ndarray
    or list), ``ok`` a bool[N] mask; ``values[i]`` is only meaningful
    where ``ok[i]``.  Own in-place writes (``addr in d.undo``) are
    accepted as-is, unvalidated and untracked, like the scalar paths.
    """
    locks = eng.locks
    with span("tm.read_bulk.lock_gather"):
        idxs = locks.index_bulk(addrs)
        ver1, _, meta1 = locks.gather(idxs)
    with span("tm.read_bulk.heap_gather"):
        vals = heap_gather(eng.heap, addrs)
    with span("tm.read_bulk.lock_gather"):
        ver2, _, meta2 = locks.gather(idxs)
    # locked-by-me also fails here: the scalar fallback resolves own locks
    # exactly (and encounter-time policies reach own writes via d.undo)
    stable = ver1 == ver2
    locked = ((meta1 | meta2) & 1) != 0
    flagged = ((meta1 | meta2) & 2) != 0
    if inclusive:
        ok = ~locked & ~flagged & stable & (ver1 <= d.r_clock)
    else:
        ok = ~locked & ~flagged & stable & (ver1 < d.r_clock)
    if d.undo:
        own = np.fromiter(d.undo.keys(), np.int64, len(d.undo))
        own_mask = np.isin(addrs, own)
        ok = ok | own_mask
    else:
        own_mask = None
    if track:
        accept = ok if own_mask is None else (ok & ~own_mask)
        sel = np.nonzero(accept)[0]
        pairs = zip(idxs[sel].tolist(), ver1[sel].tolist())
        if d.dedup_read_set:
            # traversal-level dedup (engine/traverse.py sets the flag):
            # a repeated frontier visit re-proves the same (idx, version)
            # pair — appending it again only inflates commit-time
            # revalidation.  Pairs are deduped, not bare indices: the
            # same index at a DIFFERENT version must still be tracked
            # (V_EQ revalidates against the version seen).
            seen = d.read_set_seen
            rs = d.read_set
            for p in pairs:
                if p not in seen:
                    seen.add(p)
                    rs.append(p)
        else:
            d.read_set.extend(pairs)
    return vals, ok


def finish_with_scalar(eng, d, addrs: np.ndarray, vals, ok, scalar_read):
    """Materialize the batch result: accepted elements from the gather,
    everything else re-read through ``scalar_read(eng, d, addr)`` (which
    spins / extends / aborts with the policy's exact semantics).  Returns
    the gathered ndarray untouched on a clean batch (the fast path the
    eval scans sum over), a list when any element was re-read."""
    if bool(ok.all()):
        return vals
    out = vals if isinstance(vals, list) else vals.tolist()
    redo = np.nonzero(~ok)[0]
    with span("tm.read_bulk.scalar_walk", words=int(redo.size)):
        for i in redo:
            out[i] = scalar_read(eng, d, int(addrs[i]))
    return out
