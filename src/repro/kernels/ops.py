"""jit'd public wrappers over the Pallas kernels.

These adapt engine and model-layer shapes (ragged batches, GQA heads,
parameter pytrees, ring dicts) to the flat kernel interfaces, and own
the one dispatch decision: ``on_tpu()``.  On a TPU every wrapper calls
its kernel compiled for the chip; elsewhere the engine sites keep their
numpy twins, and the wrappers that stand on the CPU path themselves
(``commit_fused``, ``publish_row``, ``snapshot_select`` and the model
kernels) take their twin or pure-jnp reference.  ``interpret=True``
runs a kernel in the Pallas interpreter instead; only tests ask for it.

``COUNTS`` records, per kernel, how often a wrapper entered the kernel
branch, how often a batch took the int64 numpy twin instead, and the
host bytes each launch uploaded and copied back.  Each launch is the
span ``kernel.<name>`` (``repro.runtime.tracing``) with ``n``, the
batch's elements, and ``h2d_bytes``.
"""
from __future__ import annotations

import collections
import functools
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import commit_fused as _cf
from repro.kernels import fused_adamw as _fa
from repro.kernels import flash_attention as _fl
from repro.kernels import gather_read as _gr
from repro.kernels import ref as _ref
from repro.kernels import scatter_write as _sw
from repro.kernels import snapshot_select as _ss
from repro.kernels import ssd_scan as _ssd
from repro.kernels import validate as _val
from repro.kernels import version_select as _vs
from repro.runtime.tracing import span

#: lane width of every lane-dense kernel layout
LANES = 128
#: smallest kernel batch: one (8, 128) int32 block
MIN_TILE = 8 * LANES
_LO32, _HI32 = -(1 << 31) + 1, (1 << 31) - 1

# the donated publish paths below request buffer donation unconditionally
# (on TPU it makes the heap/ring update in-place); the CPU backend cannot
# honor it and warns per call — scope the filter to exactly that message
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@functools.cache
def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: the kernels then run
    compiled for the chip.  The only place the platform is consulted."""
    return jax.default_backend() == "tpu"


class KernelCounts:
    """Per-kernel counts of kernel-branch entries, int64 twin routes
    (batches holding words beyond int32, which the x64-less device path
    would truncate), host bytes uploaded for launches (``h2d_bytes``)
    and device bytes copied back to the host (``d2h_bytes``); the key
    ``heap_upload`` counts ``ArrayHeap.jnp()`` copies of the word heap.
    Thread-safe; ``reset`` starts a new window."""

    def __init__(self):
        self._lock = threading.Lock()
        self.entries = collections.Counter()
        self.twin_routes = collections.Counter()
        self.h2d_bytes = collections.Counter()
        self.d2h_bytes = collections.Counter()

    def enter(self, kernel: str) -> None:
        with self._lock:
            self.entries[kernel] += 1

    def twin(self, kernel: str) -> None:
        with self._lock:
            self.twin_routes[kernel] += 1

    def moved(self, kernel: str, h2d: int = 0, d2h: int = 0) -> None:
        """Count ``h2d`` bytes uploaded and ``d2h`` copied back."""
        with self._lock:
            self.h2d_bytes[kernel] += h2d
            self.d2h_bytes[kernel] += d2h

    def reset(self) -> None:
        with self._lock:
            for c in (self.entries, self.twin_routes, self.h2d_bytes,
                      self.d2h_bytes):
                c.clear()


COUNTS = KernelCounts()


def beyond_int32(a) -> bool:
    """True when an int64 array holds a value an int32 cannot."""
    a = np.asarray(a)
    return bool(a.dtype == np.int64 and a.size
                and (int(a.max()) > _HI32 or int(a.min()) < _LO32))


def tile_for(n: int, tile: int) -> int:
    """Elements per grid step for an ``n``-element batch: a power of two
    from one (8, 128) block up to ``tile``, never below one block (the
    chip refuses smaller blocks), so ragged batches pad up to it."""
    return max(MIN_TILE, min(tile, 1 << (max(n, 1) - 1).bit_length()))


def _padded(x, length: int, fill):
    """``x`` (host or device) padded at the end to ``length`` elements
    along axis 0."""
    pad = length - x.shape[0]
    if not pad:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    if isinstance(x, np.ndarray):
        return np.pad(x, widths, constant_values=fill)
    return jnp.pad(x, widths, constant_values=fill)


def _rows(x):
    """[n] -> [ceil(n / 128), 128], zero-padded (a heap row's layout)."""
    n = x.shape[0]
    return _padded(x, -(-n // LANES) * LANES, 0).reshape(-1, LANES)


def _sorted_batch(addrs, values, n_words: int, tile: int):
    """Sort a write batch by address (the scatter kernels walk rows in
    order) and pad it to a whole number of tiles with ``n_words``, an
    address the kernels skip.  Returns ``(order, addrs, values, tile)``
    with ``addrs`` int64 and ``tile`` the grid step."""
    order = np.argsort(addrs, kind="stable")
    n = addrs.shape[0]
    t = tile_for(n, tile)
    length = -(-max(n, 1) // t) * t
    a = _padded(np.asarray(addrs, np.int64)[order], length, n_words)
    v = _padded(np.asarray(values)[order], length, 0)
    return order, a, v, t


def flash_attention(q, k, v, *, causal: bool, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B, S, H, D]; k, v: [B, Sk, KV, D] -> [B, S, H, D] (GQA)."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * H, Sk, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * H, Sk, D)
    if interpret or on_tpu():
        o = _fl.flash_attention_nhd(qf, kf, vf, causal=causal,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)
    else:
        o = _ref.flash_attention_ref(qf, kf, vf, causal=causal)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def ssd_scan(xh, dt, A, B_, C_, *, chunk: int = 256, init_state=None,
             interpret: bool = False):
    """Kernel chunk-scan; final state recomputed via the jnp path when a
    carry is required (see ssd_scan.py)."""
    assert init_state is None, "kernel path serves the no-carry hot loop"
    if not (interpret or on_tpu()):
        return _ref.ssd_scan_ref(xh, dt, A, B_, C_)[0], None
    y = _ssd.ssd_scan_pallas(xh, dt, A, B_, C_, chunk=chunk,
                             interpret=interpret)
    return y, None


def snapshot_select(ring, ts, read_clock, *, interpret: bool = False):
    """ring: [R, *shape] -> (value [*shape], ok)."""
    R = ring.shape[0]
    shape = ring.shape[1:]
    flat = ring.reshape(R, -1)
    if interpret or on_tpu():
        COUNTS.enter("snapshot_select")
        with span("kernel.snapshot_select", n=int(flat.shape[1]),
                  h2d_bytes=0):
            val, ok = _ss.snapshot_select_flat(flat, ts, read_clock,
                                               interpret=interpret)
    else:
        val, ok = _ref.snapshot_select_ref(flat, ts, read_clock)
    return val.reshape(shape), ok


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gather(heap, addrs, *, tile, interpret):
    return _gr.gather_read_flat(_rows(heap), addrs, tile=tile,
                                interpret=interpret).reshape(-1)


def snapshot_read(heap, addrs, tile: int = 1024, *,
                  interpret: bool = False):
    """Batched snapshot read: ``heap[addrs]`` in one gather launch.

    ``heap``: [H] of a 32-bit dtype, or a host int64 heap of int32-range
    words (one holding a wider word raises OverflowError: the device copy
    would truncate it, and the engine routes such heaps to the numpy
    twin first); ``addrs``: [N] int — returns the [N] gathered values as
    a jax array.  Adapts ragged batch lengths to the tiled kernel by
    padding with address 0 (always allocated — the heaps burn it as
    NULL) and slicing the result back to N.  This is the
    `Txn.read_bulk` / `snapshot_bulk` hot path on
    TPU; on CPU the engine uses the numpy twin (a single fancy-index in
    ``engine.bulkread.heap_gather``) directly.
    """
    n = int(addrs.shape[0])
    if isinstance(heap, np.ndarray) and beyond_int32(heap):
        raise OverflowError("heap holds a word beyond int32")
    hj = jnp.asarray(heap)
    if n == 0:
        return jnp.zeros((0,), hj.dtype)
    COUNTS.enter("gather_read")
    with span("kernel.gather_read", n=n) as sp:
        t = tile_for(n, tile)
        a = _padded(np.asarray(addrs, np.int32), -(-n // t) * t,
                    _gr.PAD_ADDR)
        h2d = a.nbytes + (0 if isinstance(heap, jax.Array) else hj.nbytes)
        COUNTS.moved("gather_read", h2d=h2d)
        sp.set(h2d_bytes=h2d)
        return _gather(hj, jnp.asarray(a), tile=t, interpret=interpret)[:n]


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("tile", "interpret"))
def _scatter(row, addrs, values, *, tile, interpret):
    n = row.shape[0]
    out = _sw.scatter_write_flat(_rows(row), addrs, values, n_words=n,
                                 tile=tile, interpret=interpret)
    return out.reshape(-1)[:n]


def _scatter_launch(row, addrs_np, values, tile, interpret):
    """Sort, pad and launch one scatter kernel over a jax row."""
    COUNTS.enter("scatter_write")
    with span("kernel.scatter_write", n=int(addrs_np.shape[0])) as sp:
        _, a, v, t = _sorted_batch(addrs_np, np.asarray(values),
                                   int(row.shape[0]), tile)
        a32, vr = np.asarray(a, np.int32), np.asarray(v, row.dtype)
        COUNTS.moved("scatter_write", h2d=a32.nbytes + vr.nbytes)
        sp.set(h2d_bytes=a32.nbytes + vr.nbytes)
        return _scatter(row, jnp.asarray(a32), jnp.asarray(vr), tile=t,
                        interpret=interpret)


def write_back(heap, addrs, values, tile: int = 1024, *,
               interpret: bool = False):
    """Batched commit write-back: ``heap[addrs] = values`` in one launch.

    ``heap``: [H] (a 32-bit dtype, or an int64 host heap); ``addrs``:
    [N] int (unique — write sets are dict-keyed); ``values``: [N] —
    returns the [H] updated row as an ndarray.  Guards the int64 range
    per the ``version_select`` pattern: without jax x64 the kernel would
    silently truncate int64 payloads — AND addresses — to int32, so such
    batches take the numpy twin (``scatter_write.np_write_back``, exact
    at any width) instead; an out-of-range address then raises there
    rather than truncating and scattering to the wrong word.
    """
    vals = np.asarray(values)
    addrs_np = np.asarray(addrs, np.int64)
    if addrs_np.shape[0] == 0:
        return np.array(np.asarray(heap), copy=True)
    # heap CONTENTS are scanned only for host-side heaps: a jax int64
    # heap can only exist with x64 enabled, where ``jnp.asarray`` cannot
    # truncate it — so the device hot path (``scatter_row``) never pays
    # a device->host heap copy or an O(heap) reduction here.  The
    # addr/value guards stay unconditional: their int32 casts below are
    # explicit and would truncate regardless of x64.
    if not isinstance(heap, (np.ndarray, jax.Array)):
        heap = np.asarray(heap)            # lists/tuples: normalize once
    heap_np = heap if isinstance(heap, np.ndarray) else None
    if beyond_int32(vals) or beyond_int32(addrs_np) \
            or (heap_np is not None and beyond_int32(heap_np)):
        COUNTS.twin("scatter_write")
        return _sw.np_write_back(np.asarray(heap), addrs_np, vals)
    hj = jnp.array(heap)                   # a copy: the launch donates it
    if heap_np is not None:
        COUNTS.moved("scatter_write", h2d=hj.nbytes)
    out = np.asarray(_scatter_launch(hj, addrs_np, vals, tile, interpret))
    COUNTS.moved("scatter_write", d2h=out.nbytes)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _publish_row_xla(row, addrs, values):
    return row.at[addrs].set(values)


def publish_row(row, addrs, values, tile: int = 1024, *,
                interpret: bool = False):
    """Device-resident row publish: ``row.at[addrs].set(values)`` with
    the input row DONATED.

    The donation contract ``write_back`` cannot offer: that wrapper
    returns an ndarray (a device->host heap copy per call), which is
    fine for the in-place numpy engine heap but wrong for a commit path
    whose row should never leave the device.  Here the result stays a
    jax array, the jit requests donation of the row buffer (in-place on
    backends that honor it; the CPU backend ignores the request), and
    no host materialization of the row happens at any width the caller
    admits.  On TPU the scatter_write kernel runs; elsewhere the jitted
    jnp scatter.  The caller owns the bounds check and the int64-range
    guard (``scatter_row`` routes guarded batches to the numpy twin) —
    and, on device runtimes, ownership of ``row``: a donated buffer is
    invalidated, so snapshot-pinned readers must be handed a fresh
    alias first (see ``MVStoreHandle._install``).
    """
    a_np = np.asarray(addrs, np.int64)
    rj = jnp.asarray(row)
    if a_np.shape[0] == 0:
        return rj
    if interpret or on_tpu():
        return _scatter_launch(rj, a_np, values, tile, interpret)
    return _publish_row_xla(rj, jnp.asarray(a_np),
                            jnp.asarray(values, rj.dtype))


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("mode", "tile", "interpret"))
def _commit_fused_jit(heap, wa, wv, ws, lv, lo, lm, ls,
                      rv, ro, rm, rn, rs, tids, rcs, cv, *, mode, tile,
                      interpret):
    n = heap.shape[0]
    out, ok, lver = _cf.commit_fused_flat(
        _rows(heap), wa, wv, ws, lv, lo, lm, ls, rv, ro, rm, rn, rs,
        tids, rcs, cv, n_words=n, mode=mode, tile=tile,
        interpret=interpret)
    return out.reshape(-1)[:n], ok, lver


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _ring_refresh(ring, ring_ts, row, slot, ts):
    new_ring = jax.lax.dynamic_update_index_in_dim(
        ring, row.astype(ring.dtype), slot, 0)
    new_ts = jax.lax.dynamic_update_index_in_dim(
        ring_ts, ts.astype(ring_ts.dtype), slot, 0)
    return new_ring, new_ts


def commit_fused(heap, w_addr, w_val, w_seg,
                 l_words, l_seg, r_words, r_seen, r_seg,
                 tids, r_clocks, commit_ver, n_txn, *,
                 mode=None, tile: int = 1024,
                 ring=None, ring_ts=None, ring_slot=None,
                 interpret: bool = False):
    """Group-commit megakernel: validate + claim-check + scatter + stamp
    for a batch of conflict-disjoint transactions in ONE launch.

    ``heap``: [H]; write batch ``(w_addr, w_val, w_seg)``: [N] flat
    segment layout (``commit_fused.pack_segments``); ``l_words``/
    ``r_words``: raw packed int64 lock words for the write-lock and
    read-set entries (gathered by the caller under its atomicity
    bracket), with ``l_seg``/``r_seg`` owner segments and ``r_seen``
    the versions recorded at read time; ``tids``/``r_clocks``: [T]
    per-member identity and snapshot.  Returns ``(new_heap, txn_ok,
    new_l_words)`` — ``new_heap`` a jax array (device-resident, heap
    buffer donated; never materialized to host here; the exact ndarray
    when the batch routes to the int64 numpy twin), ``txn_ok`` a
    bool[n_txn] ndarray, ``new_l_words`` exact int64 release words:
    ``commit_ver`` stamped unlocked where the member survived, the
    original word otherwise.  With ``ring``/``ring_ts``/``ring_slot``
    given, the version-ring row refresh rides the same call (donated;
    the MVStore publish path — its commit lock is the held seqlock) and
    two more values ``(new_ring, new_ring_ts)`` are returned.

    Versions are REBASED to ``commit_ver`` before the int32 cast (the
    ``validate_readset`` treatment — the predicates only compare
    deltas) and the release words are reconstructed host-side at full
    width; batches whose payloads/addresses exceed int32 route to the
    in-file numpy twin (``np_commit_fused``) exactly like
    ``write_back``, as does an int64-range host heap.  Off the TPU the
    twin serves every batch (its result returns to the heap's device
    when the heap was a jax array).
    """
    from repro.core.engine.arrayheap import (_UNLOCKED_WORD, _VER_SHIFT,
                                             unpack_words)

    if mode is None:
        mode = _cf.MODE_LE
    base = int(commit_ver)
    l_ver, l_own, l_meta = unpack_words(l_words)
    r_ver, r_own, r_meta = unpack_words(r_words)
    w_addr = np.asarray(w_addr, np.int64)
    w_seg = np.asarray(w_seg, np.int64)
    l_seg = np.asarray(l_seg, np.int64)
    r_seg = np.asarray(r_seg, np.int64)
    r_seen = np.asarray(r_seen, np.int64)
    vals = np.asarray(w_val)

    def stamp(ok):
        return np.where(ok[l_seg] if l_seg.size else np.zeros((0,), bool),
                        (np.int64(base) << _VER_SHIFT)
                        | np.int64(_UNLOCKED_WORD),
                        np.asarray(l_words, np.int64))

    if not isinstance(heap, (np.ndarray, jax.Array)):
        heap = np.asarray(heap)
    heap_np = heap if isinstance(heap, np.ndarray) else None
    wide = beyond_int32(vals) or beyond_int32(w_addr) \
        or (heap_np is not None and beyond_int32(heap_np))
    if wide or not (interpret or on_tpu()):
        if wide:
            COUNTS.twin("commit_fused")
        new_heap, ok, _ = _cf.np_commit_fused(
            np.asarray(heap), w_addr, vals, w_seg,
            l_ver, l_own, l_meta, l_seg,
            r_ver, r_own, r_meta, r_seen, r_seg,
            tids, r_clocks, base, n_txn, mode)
        # an int64 batch stays numpy: jnp.asarray without x64 would
        # truncate the very payloads that routed it here
        if not wide and heap_np is None:
            new_heap = jax.device_put(new_heap, heap.sharding)
        out = (new_heap, ok, stamp(ok))
    else:
        COUNTS.enter("commit_fused")
        with span("kernel.commit_fused", n=int(w_addr.shape[0])) as sp:
            hj = jnp.asarray(heap)
            h2d = 0 if heap_np is None else hj.nbytes
            order, a32, v, t = _sorted_batch(w_addr, vals,
                                             int(hj.shape[0]), tile)
            s32 = _padded(w_seg[order], a32.shape[0], 0)

            def rel(x):
                return np.clip(np.asarray(x, np.int64) - base, _LO32,
                               _HI32)

            # dummy txn slot T absorbs the pad rows of empty side batches
            tids_p = np.concatenate([np.asarray(tids, np.int64), [0]])
            rcs_p = np.concatenate([rel(r_clocks), [0]])
            dummy = len(tids_p) - 1

            def side(ver_rel, own, meta, seen_rel, seg):
                if seg.size:
                    return ver_rel, own, meta, seen_rel, seg
                z = np.zeros(1, np.int64)
                return z, z.astype(np.int32), z.astype(np.int32), z, \
                    np.full(1, dummy, np.int64)

            lv, lo_, lm, _, ls = side(rel(l_ver), l_own, l_meta,
                                      np.zeros_like(l_ver), l_seg)
            rv, ro, rm, rn, rs = side(rel(r_ver), r_own, r_meta,
                                      rel(r_seen), r_seg)

            def up(x, dtype=np.int32):
                # cast on the host: the upload is then the bytes counted
                # here, and no convert program runs on the device
                nonlocal h2d
                x = np.asarray(x).astype(dtype, copy=False)
                h2d += x.nbytes
                return jnp.asarray(x)

            new_heap, ok32, _ = _commit_fused_jit(
                hj, up(a32), up(v, hj.dtype), up(s32),
                up(lv), up(lo_), up(lm), up(ls),
                up(rv), up(ro), up(rm), up(rn), up(rs),
                up(tids_p), up(rcs_p), jnp.zeros((1,), jnp.int32),
                mode=int(mode), tile=t, interpret=interpret)
            COUNTS.moved("commit_fused", h2d=h2d, d2h=4 * n_txn)
            sp.set(h2d_bytes=h2d)
        ok = np.asarray(ok32[:n_txn]) != 0
        out = (new_heap, ok, stamp(ok))
    if ring is None:
        return out
    new_heap, ok, new_l = out
    new_ring, new_ts = _ring_refresh(
        jnp.asarray(ring), jnp.asarray(ring_ts), jnp.asarray(new_heap),
        jnp.asarray(int(ring_slot), jnp.int32),
        jnp.asarray(np.int64(base) if ring_ts.dtype == np.int64
                    else np.int32(base)))
    return new_heap, ok, new_l, new_ring, new_ts


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _validate(ver, own, meta, seen, params, *, tile, interpret):
    mask = _val.validate_readset_flat(
        ver.reshape(-1, LANES), own.reshape(-1, LANES),
        meta.reshape(-1, LANES), seen.reshape(-1, LANES),
        params[0], params[1], params[2], tile=tile, interpret=interpret)
    return jnp.all(mask == 1)


def validate_readset(ver, own, meta, seen, r_clock, tid, mode,
                     tile: int = 1024, *, interpret: bool = False) -> bool:
    """Bulk read-set validation: True iff every entry is still valid.

    Adapts ragged read-set lengths to the tiled kernel by padding with
    always-valid entries (see ``validate.PAD``), then AND-reduces the
    per-entry mask.  The engine calls this on the TPU path; on CPU it
    uses the numpy twin directly.

    Versions are rebased to ``r_clock`` before the int32 cast: the packed
    lock word carries a 46-bit version and the clock bumps on every
    commit AND abort, so absolute versions can exceed int32 in long runs
    — but every predicate only compares versions against ``r_clock`` or
    ``seen``, and within one transaction's lifetime those deltas are
    tiny.  The clip is a belt-and-braces clamp that preserves the
    comparison's sign (a clamped entry is >= 2^31 commits away from the
    snapshot, i.e. unambiguously stale/fresh).
    """
    n = int(ver.shape[0])
    if n == 0:
        return True
    COUNTS.enter("validate")
    base = int(r_clock)
    t = tile_for(n, tile)
    length = -(-n // t) * t
    p = _val.PAD
    # four int32 columns of ``length`` entries and the three params
    h2d = 4 * 4 * length + 4 * 3
    COUNTS.moved("validate", h2d=h2d, d2h=1)

    def prep(x, fill):
        return jnp.asarray(_padded(np.asarray(x).astype(np.int32), length,
                                   fill))

    def rel(x):
        return np.clip(np.asarray(x, np.int64) - base, _LO32, _HI32)

    with span("kernel.validate", n=n, h2d_bytes=h2d):
        return bool(_validate(
            prep(rel(ver), p["ver"]), prep(own, p["own"]),
            prep(meta, p["meta"]), prep(rel(seen), p["seen"]),
            jnp.asarray([0, int(tid), int(mode)], jnp.int32),
            tile=t, interpret=interpret))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _version_select(ts, data, *, tile, interpret):
    depth = ts.shape[1]
    slots = lambda x: x.T.reshape(depth, -1, LANES)    # noqa: E731
    vals, ok = _vs.version_select_flat(slots(ts), slots(data), 0,
                                       tile=tile, interpret=interpret)
    return vals.reshape(-1), ok.reshape(-1)


def version_select(ts, data, r_clock, tile: int = 1024, *,
                   interpret: bool = False):
    """Batched snapshot version select over packed VLT mirror rows.

    ``ts``/``data``: [N, D] newest-first (timestamps int, data numeric);
    returns ``(values [N] ndarray, ok [N] bool)`` — per row, the newest
    ``data`` whose timestamp is strictly below ``r_clock`` and whether
    any slot qualified.  Adapts ragged batch sizes to the tiled kernel
    by padding with always-invalid rows and rebases timestamps to
    ``r_clock`` before the int32 cast (absolute clocks exceed int32 in
    long runs; only the sign of ``ts - r_clock`` matters — same
    treatment as ``validate_readset``).  This is the Mode-U bulk
    versioned-read hot path on TPU; on CPU the engine uses the numpy
    twin (``core.vlt.np_version_select``) directly.
    """
    n = int(ts.shape[0])
    if n == 0:
        return (np.zeros((0,), np.int64), np.zeros((0,), bool))
    data = np.asarray(data)
    if beyond_int32(data):
        # without jax x64 the kernel would silently truncate int64
        # payloads to int32 — wrong values with ok=True; such batches
        # take the numpy twin (exact at any width) instead
        from repro.core.vlt import np_version_select
        COUNTS.twin("version_select")
        return np_version_select(np.asarray(ts, np.int64), data,
                                 int(r_clock))
    COUNTS.enter("version_select")
    with span("kernel.version_select", n=n) as sp:
        rel = np.clip(np.asarray(ts, np.int64) - int(r_clock), _LO32,
                      _HI32)
        t = tile_for(n, tile)
        length = -(-n // t) * t
        ts32 = _padded(rel.astype(np.int32), length, _vs.PAD_TS)
        data32 = _padded(data, length, 0).astype(np.int32, copy=False)
        h2d = ts32.nbytes + data32.nbytes
        sp.set(h2d_bytes=h2d)
        vals, ok = _version_select(jnp.asarray(ts32), jnp.asarray(data32),
                                   tile=t, interpret=interpret)
        vals, ok = np.asarray(vals[:n]), np.asarray(ok[:n])
    COUNTS.moved("version_select", h2d=h2d, d2h=vals.nbytes + ok.nbytes)
    return vals, ok != 0


def fused_adamw(p, g, m, v, ring, slot, *, lr, scale, count, b1, b2, eps,
                wd, interpret: bool = False):
    """Pytree-leaf fused update.  p: any shape; ring: [R, *p.shape]|None."""
    shape = p.shape
    n = p.size
    cnt = count.astype(jnp.float32)
    b1c = 1 - b1 ** cnt
    b2c = 1 - b2 ** cnt
    rf = ring.reshape(ring.shape[0], n) if ring is not None else None
    if interpret or on_tpu():
        tile = n
        for cand in (2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
            if n % cand == 0:
                tile = cand
                break
        p2, m2, v2, r2 = _fa.fused_adamw_flat(
            p.reshape(n), g.reshape(n), m.reshape(n), v.reshape(n), rf,
            jnp.asarray(slot, jnp.int32), lr=jnp.asarray(lr),
            scale=jnp.asarray(scale), b1c=b1c, b2c=b2c, b1=b1, b2=b2,
            eps=eps, wd=wd, tile=tile, interpret=interpret)
    else:
        p2, m2, v2, r2 = _ref.fused_adamw_ref(
            p.reshape(n), g.reshape(n), m.reshape(n), v.reshape(n), rf,
            slot, lr=lr, scale=scale, b1c=b1c, b2c=b2c, b1=b1, b2=b2,
            eps=eps, wd=wd)
    p2 = p2.reshape(shape)
    m2 = m2.reshape(shape)
    v2 = v2.reshape(shape)
    if ring is not None:
        r2 = r2.reshape(ring.shape)
    return p2, m2, v2, r2
