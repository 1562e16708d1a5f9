"""Fused commit megakernel — one launch per batch of disjoint transactions.

``scatter_write.py`` made the WRITE-BACK of one commit a single launch;
this kernel fuses the whole commit decision for a GROUP of
conflict-disjoint transactions:

    validate each member's read-set lock words        (validate.py math)
  + check each member's write locks are claimable      (try_lock_bulk math)
  + scatter every surviving member's values            (scatter_write math)
  + stamp the release versions for the claimed words

in ONE launch over a segment-offset layout: ragged per-transaction
read/write sets are packed into flat ``(addrs, values, txn_id)`` /
``(lock fields, txn_id)`` batches (``pack_segments`` below), and a
per-transaction verdict is accumulated as a running minimum in an
``ok`` vector — a member is publishable iff EVERY one of its read
entries validates and EVERY one of its write locks is free.

Layout mirrors the scatter kernel: the heap stays in HBM as
``[H / 128, 128]`` rows aliased to the output, the write batch rides in
SMEM tiles sorted by address, and the (small) read/lock/txn vectors are
whole SMEM blocks.  Grid step 0 computes the verdict with scalar loops
(a running per-member minimum — a member survives iff every entry it
owns passes) and stamps the release versions; every step then scatters
its write tile through ``scatter_write.scatter_rows``, skipping the
entries of FAILED members, so a failed member's writes never touch the
heap.

The caller owns atomicity: on the CPU engine the covering lock stripes
are held around the decision + claim (``groupcommit.py``); at the
MVStore layer the commit lock (the seqlock analogue) brackets the call.
Versions ride in REBASED to the commit version and clipped to int32
(the ``validate.py`` treatment — only deltas matter to the predicates);
``ops.commit_fused`` reconstructs exact int64 release words host-side.

``np_commit_fused`` is the in-file numpy twin (exact at any width, the
CPU-production path); ``np_commit_decide`` is its verdict half, shared
with the engine's group-commit pipeline, which scatters through the
in-place heap instead of the functional row.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scatter_write import LANES, scatter_rows
# fault injection only (stdlib-only module — keeps the kernels
# engine-import-free): np_commit_fused splits its scatter around the
# ``mid_scatter`` point so crash drills can freeze a partial-lane image
from repro.reliability import faultpoints as FP

# validation predicate selectors — same encoding as engine/validation.py
# (kernels stay engine-import-free, so the constants are mirrored here
# and pinned equal by tests/test_groupcommit.py)
MODE_LT = 0      # version <  r_clock   (Multiverse / DCTL deferred clock)
MODE_LE = 1      # version <= r_clock   (TL2-style commit-bumped clock)
MODE_EQ = 2      # version == seen      (TinySTM timestamp extension)


def pack_segments(per_txn) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Ragged per-transaction vectors -> one flat batch + segment ids.

    ``per_txn`` is a list of 1-D arrays (one per transaction, any
    lengths including zero).  Returns ``(flat, seg, offsets)`` where
    ``flat`` is the concatenation, ``seg[i]`` is the transaction index
    owning ``flat[i]``, and ``offsets`` is the int64[T+1] segment-offset
    vector (``flat[offsets[t]:offsets[t+1]]`` is transaction ``t``'s
    slice — the round-trip ``tests/test_groupcommit.py`` pins).
    """
    arrs = [np.asarray(a) for a in per_txn]
    lens = np.fromiter((a.shape[0] for a in arrs), np.int64, len(arrs))
    offsets = np.zeros(len(arrs) + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    flat = (np.concatenate(arrs) if arrs
            else np.zeros((0,), np.int64))
    seg = np.repeat(np.arange(len(arrs), dtype=np.int64), lens)
    return flat, seg, offsets


def np_commit_decide(l_ver, l_own, l_meta, l_seg,
                     r_ver, r_own, r_meta, r_seen, r_seg,
                     tids, r_clocks, n_txn: int, mode: int) -> np.ndarray:
    """Per-transaction verdict: bool[n_txn], True iff every read entry
    validates (at the member's OWN ``r_clock``/mode) and every write
    lock is claimable (free and unflagged, or already held by the
    member — ``try_lock_bulk``'s conflict rule).  Field layout matches
    ``ArrayLockTable.gather``: meta bit0 = locked, bit1 = flag.
    """
    tids = np.asarray(tids, np.int64)
    r_clocks = np.asarray(r_clocks, np.int64)
    ok = np.ones(n_txn, bool)
    r_seg = np.asarray(r_seg, np.int64)
    if r_seg.size:
        ver = np.asarray(r_ver, np.int64)
        meta = np.asarray(r_meta)
        locked = (meta & 1) != 0
        flagged = (meta & 2) != 0
        mine = locked & (np.asarray(r_own) == tids[r_seg])
        rc = r_clocks[r_seg]
        if mode == MODE_LT:
            valid = mine | (~locked & ~flagged & (ver < rc))
        elif mode == MODE_LE:
            valid = (~locked | mine) & (ver <= rc)
        else:
            valid = (~locked | mine) & (ver == np.asarray(r_seen, np.int64))
        # scatter-AND via a bincount of FAILURES: the common all-valid
        # batch reduces an empty array (ufunc.at would walk every entry)
        ok &= np.bincount(r_seg[~valid], minlength=n_txn) == 0
    l_seg = np.asarray(l_seg, np.int64)
    if l_seg.size:
        meta = np.asarray(l_meta)
        locked = (meta & 1) != 0
        flagged = (meta & 2) != 0
        own = locked & (np.asarray(l_own) == tids[l_seg])
        claimable = ~((locked | flagged) & ~own)
        ok &= np.bincount(l_seg[~claimable], minlength=n_txn) == 0
    return ok


def np_commit_fused(heap, w_addr, w_val, w_seg,
                    l_ver, l_own, l_meta, l_seg,
                    r_ver, r_own, r_meta, r_seen, r_seg,
                    tids, r_clocks, commit_ver: int, n_txn: int,
                    mode: int = MODE_LE):
    """Numpy twin: ``(new_heap, txn_ok, new_l_ver)`` — exact at any
    integer width (the wrapper routes int64-range batches here, the
    ``write_back`` guard pattern).

    ``new_heap`` is a copy with every SURVIVING member's ``(addr, val)``
    entries applied; failed members leave no trace.  ``new_l_ver[e]`` is
    the release version for write-lock entry ``e``: ``commit_ver`` where
    the owning member survived, the entry's original version otherwise.
    Addresses must be in range; a negative one raises (it would wrap
    under fancy indexing) exactly like ``np_write_back``.
    """
    ok = np_commit_decide(l_ver, l_own, l_meta, l_seg,
                          r_ver, r_own, r_meta, r_seen, r_seg,
                          tids, r_clocks, n_txn, mode)
    l_seg = np.asarray(l_seg, np.int64)
    new_l_ver = np.where(ok[l_seg] if l_seg.size else
                         np.zeros((0,), bool),
                         np.int64(commit_ver), np.asarray(l_ver, np.int64))
    out = np.array(heap, copy=True)
    w_seg = np.asarray(w_seg, np.int64)
    if w_seg.size:
        sel = ok[w_seg]
        a = np.asarray(w_addr, np.int64)[sel]
        if a.size and int(a.min(initial=0)) < 0:
            raise IndexError(int(a.min()))
        v = np.asarray(w_val)[sel]
        if FP.ACTIVE is not None and a.size > 1:
            # partial-lane completion fault: half the surviving lanes
            # land, then the injection point — a crash here freezes the
            # batch mid-scatter, the torn image whole-record idempotent
            # WAL redo must heal (the caller's claim words are already
            # stamped, so in-process recovery rolls the group forward)
            h = a.size // 2
            out[a[:h]] = v[:h]
            FP.fire("mid_scatter",
                    int(np.asarray(tids)[0]) if len(tids) else -1)
            out[a[h:]] = v[h:]
        else:
            out[a] = v
    return out, ok, new_l_ver


def _fused_kernel(mode, n_words,
                  wa_ref, wv_ref, ws_ref,
                  lv_ref, lo_ref, lm_ref, ls_ref,
                  rv_ref, ro_ref, rm_ref, rn_ref, rs_ref,
                  tid_ref, rc_ref, cv_ref, heap_in,
                  heap_hbm, o_ok, o_lver, row, sem):
    del heap_in                      # aliased to heap_hbm

    def bit(cond):
        return jnp.where(cond, 1, 0)

    # step 0: the whole verdict as scalar loops over the SMEM read/lock
    # batches, then the release versions
    @pl.when(pl.program_id(0) == 0)
    def _decide():
        def init(t, c):
            o_ok[t] = 1
            return c

        jax.lax.fori_loop(0, o_ok.shape[0], init, 0)

        def read_entry(e, c):
            seg = rs_ref[e]
            meta = rm_ref[e]
            locked = meta & 1
            flagged = (meta >> 1) & 1
            mine = locked & bit(ro_ref[e] == tid_ref[seg])
            ver = rv_ref[e]
            if mode == MODE_LT:
                valid = mine | ((1 - locked) & (1 - flagged)
                                & bit(ver < rc_ref[seg]))
            elif mode == MODE_LE:
                valid = ((1 - locked) | mine) & bit(ver <= rc_ref[seg])
            else:
                valid = ((1 - locked) | mine) & bit(ver == rn_ref[e])
            o_ok[seg] = jnp.minimum(o_ok[seg], valid)
            return c

        jax.lax.fori_loop(0, rs_ref.shape[0], read_entry, 0)

        def lock_entry(e, c):
            seg = ls_ref[e]
            meta = lm_ref[e]
            locked = meta & 1
            held = (locked | ((meta >> 1) & 1)) \
                & (1 - (locked & bit(lo_ref[e] == tid_ref[seg])))
            o_ok[seg] = jnp.minimum(o_ok[seg], 1 - held)
            return c

        jax.lax.fori_loop(0, ls_ref.shape[0], lock_entry, 0)

        def stamp(e, c):
            o_lver[e] = jnp.where(o_ok[ls_ref[e]] == 1, cv_ref[0],
                                  lv_ref[e])
            return c

        jax.lax.fori_loop(0, ls_ref.shape[0], stamp, 0)

    # every step (incl. 0, after the decide above): scatter this write
    # tile; a failed member's writes are skipped, so they never land
    scatter_rows(heap_hbm, row, sem, n_words, wa_ref, wv_ref,
                 lambda i: o_ok[ws_ref[i]] == 1)


def commit_fused_flat(heap, w_addr, w_val, w_seg,
                      l_ver, l_own, l_meta, l_seg,
                      r_ver, r_own, r_meta, r_seen, r_seg,
                      tids, r_clocks, commit_ver, *, n_words: int,
                      mode: int = MODE_LE, tile: int = 1024,
                      interpret: bool = False):
    """heap: [R, 128] rows of a 32-bit dtype; write batch [N] (N a
    multiple of ``tile``, int32 addrs ASCENDING and segs, values
    heap.dtype); lock batch [L]; read batch [M]; txn vectors [T]
    (int32); commit_ver: [1] int32 (REBASED — 0 by the wrapper's
    convention).  Returns ``(heap' [R, 128], ok [T] int32, lver' [L]
    int32)``.  Pad rows must point their seg at a dummy txn slot
    (read/lock batches) or carry an address at or past ``n_words``
    (write batch) — ``ops.commit_fused`` owns those conventions.
    """
    n = w_addr.shape[0]
    assert heap.ndim == 2 and heap.shape[1] == LANES, heap.shape
    assert n % tile == 0, (n, tile)
    t = tids.shape[0]
    L = l_ver.shape[0]
    smem = pltpu.SMEM
    tiled = pl.BlockSpec((tile,), lambda i: (i,), memory_space=smem)
    const = lambda s: pl.BlockSpec((s,), lambda i: (0,),    # noqa: E731
                                   memory_space=smem)
    m = r_ver.shape[0]
    return pl.pallas_call(
        lambda *refs: _fused_kernel(mode, n_words, *refs),
        grid=(n // tile,),
        in_specs=[
            tiled, tiled, tiled,           # w_addr, w_val, w_seg
            const(L), const(L), const(L), const(L),   # l_*
            const(m), const(m), const(m), const(m), const(m),  # r_*
            const(t), const(t),            # tids, r_clocks
            const(1),                      # commit_ver
            pl.BlockSpec(memory_space=pl.ANY),        # heap
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), const(t), const(L)],
        out_shape=[
            jax.ShapeDtypeStruct(heap.shape, heap.dtype),
            jax.ShapeDtypeStruct((t,), jnp.int32),
            jax.ShapeDtypeStruct((L,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, LANES), heap.dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={15: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w_addr, w_val, w_seg, l_ver, l_own, l_meta, l_seg,
      r_ver, r_own, r_meta, r_seen, r_seg, tids, r_clocks, commit_ver,
      heap)
