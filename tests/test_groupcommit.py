"""Group commit + fused commit megakernel (PR 7).

Assurance layers, mirroring ``tests/test_commit_bulk.py``'s ladder:

  * packing: the ragged segment-offset layout round-trips exactly
    (``pack_segments`` offsets slice back to the inputs);
  * constants: the kernel-side MODE_* selectors are pinned equal to the
    engine's V_* validation modes (the kernels stay engine-import-free,
    so the mirror is enforced here);
  * kernel: the fused Pallas megakernel agrees with its in-file numpy
    twin element-for-element across modes, ragged batches and failed
    members — and beyond-int32 payloads route to the twin with exact
    int64 release words;
  * grouping: ``partition_disjoint`` enforces the
    ``write_i ∩ (read_j ∪ write_j) = ∅`` conflict rule (read-read
    sharing allowed, within-transaction duplicates allowed, sparse
    indices exercise the sort fallback);
  * engine: N disjoint transactions group-commit at ONE clock tick with
    serializable results identical to the solo pipeline; overlapping
    transactions degrade to exactly today's solo path; a member that
    fails validation aborts alone — claimed nothing, scattered nothing;
  * store: the MVStore publish path keeps the heap device-resident —
    no per-commit host materialization of any heap-sized array.

Plus the ``addr_lock_indices`` generator-input regression.
"""
import numpy as np
import pytest

from repro.core.engine import commit as C
from repro.core.engine import validation as V
from repro.core.engine.groupcommit import CommitBatcher, partition_disjoint
from repro.kernels import commit_fused as CF

from tests._backends import make_test_tm


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------


def test_pack_segments_roundtrip_ragged():
    parts = [np.array([5, 3, 9], np.int64), np.zeros((0,), np.int64),
             np.array([7], np.int64), np.arange(4, dtype=np.int64)]
    flat, seg, offsets = CF.pack_segments(parts)
    assert flat.shape == (8,) and seg.shape == (8,)
    assert offsets.tolist() == [0, 3, 3, 4, 8]
    for t, p in enumerate(parts):
        np.testing.assert_array_equal(flat[offsets[t]:offsets[t + 1]], p)
        assert (seg[offsets[t]:offsets[t + 1]] == t).all()


def test_pack_segments_empty_batch():
    flat, seg, offsets = CF.pack_segments([])
    assert flat.size == 0 and seg.size == 0
    assert offsets.tolist() == [0]


# ---------------------------------------------------------------------------
# constants
# ---------------------------------------------------------------------------


def test_mode_constants_pinned_to_engine():
    assert CF.MODE_LT == V.V_LT
    assert CF.MODE_LE == V.V_LE
    assert CF.MODE_EQ == V.V_EQ


# ---------------------------------------------------------------------------
# kernel vs twin
# ---------------------------------------------------------------------------


def _random_batch(rng, n_txn, h, mode):
    """A packed commit batch with a mix of passing and failing members."""
    w_parts = [rng.choice(h, size=rng.integers(0, 9), replace=False)
               .astype(np.int64) for _ in range(n_txn)]
    w_flat, w_seg, _ = CF.pack_segments(w_parts)
    w_val = rng.integers(-1000, 1000, size=w_flat.size).astype(np.int64)
    L = int(rng.integers(1, 4 * n_txn))
    M = int(rng.integers(0, 4 * n_txn))
    l_seg = rng.integers(0, n_txn, size=L).astype(np.int64)
    r_seg = rng.integers(0, n_txn, size=M).astype(np.int64)
    mk = lambda k: (rng.integers(0, 50, size=k).astype(np.int64),   # noqa: E731
                    rng.integers(-1, 5, size=k).astype(np.int32),
                    rng.integers(0, 4, size=k).astype(np.int32))
    l_ver, l_own, l_meta = mk(L)
    r_ver, r_own, r_meta = mk(M)
    r_seen = rng.integers(0, 50, size=M).astype(np.int64)
    tids = np.arange(n_txn, dtype=np.int64)
    rcs = rng.integers(0, 50, size=n_txn).astype(np.int64)
    return (w_flat, w_val, w_seg, l_ver, l_own, l_meta, l_seg,
            r_ver, r_own, r_meta, r_seen, r_seg, tids, rcs)


@pytest.mark.parametrize("mode", [CF.MODE_LT, CF.MODE_LE, CF.MODE_EQ])
def test_fused_kernel_matches_numpy_twin(mode):
    rng = np.random.default_rng(11 + mode)
    h, n_txn, cv = 64, 4, 77
    for trial in range(6):
        heap = rng.integers(-100, 100, size=h).astype(np.int32)
        (w_flat, w_val, w_seg, l_ver, l_own, l_meta, l_seg,
         r_ver, r_own, r_meta, r_seen, r_seg, tids, rcs) = \
            _random_batch(rng, n_txn, h, mode)
        want_heap, want_ok, want_lver = CF.np_commit_fused(
            heap, w_flat, w_val, w_seg, l_ver, l_own, l_meta, l_seg,
            r_ver, r_own, r_meta, r_seen, r_seg, tids, rcs,
            cv, n_txn, mode)
        # the kernel's contract: [R, 128] heap rows, the write batch
        # sorted by address and padded to a whole tile with an address
        # it skips (h); pad segs at a passing slot are irrelevant
        tile = 1024
        order = np.argsort(w_flat, kind="stable")
        pad = (-w_flat.size) % tile or tile
        a = np.concatenate([w_flat[order], np.full(pad, h, np.int64)])
        v = np.concatenate([w_val[order], np.zeros(pad, np.int64)])
        s = np.concatenate([w_seg[order], np.zeros(pad, np.int64)])

        def i32(x):
            return np.asarray(x, np.int32)

        rows = np.pad(heap, (0, (-h) % CF.LANES)).reshape(-1, CF.LANES)
        got_heap, got_ok, got_lver = CF.commit_fused_flat(
            rows, i32(a), i32(v), i32(s),
            i32(l_ver), l_own, l_meta, i32(l_seg),
            i32(r_ver), r_own, r_meta, i32(r_seen), i32(r_seg),
            i32(tids), i32(rcs), np.array([cv], np.int32),
            n_words=h, mode=mode, tile=tile, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got_heap).reshape(-1)[:h], want_heap)
        np.testing.assert_array_equal(np.asarray(got_ok) != 0, want_ok)
        np.testing.assert_array_equal(np.asarray(got_lver),
                                      want_lver.astype(np.int32))


def test_np_twin_failed_member_leaves_no_trace():
    heap = np.arange(10, dtype=np.int64)
    # txn 0 writes [2,3] and passes; txn 1 writes [7] but its lock is
    # held by a foreign owner -> fails, heap[7] untouched
    w_flat = np.array([2, 3, 7], np.int64)
    w_val = np.array([100, 200, 999], np.int64)
    w_seg = np.array([0, 0, 1], np.int64)
    l_ver = np.array([5, 5, 5], np.int64)
    l_own = np.array([-1, -1, 9], np.int32)
    l_meta = np.array([0, 0, 1], np.int32)     # bit0 locked
    l_seg = np.array([0, 0, 1], np.int64)
    z = np.zeros((0,), np.int64)
    zi = np.zeros((0,), np.int32)
    new_heap, ok, new_lver = CF.np_commit_fused(
        heap, w_flat, w_val, w_seg, l_ver, l_own, l_meta, l_seg,
        z, zi, zi, z, z, np.array([0, 1], np.int64),
        np.array([9, 9], np.int64), 42, 2, CF.MODE_LE)
    assert ok.tolist() == [True, False]
    assert new_heap[2] == 100 and new_heap[3] == 200
    assert new_heap[7] == 7                    # untouched
    assert new_lver.tolist() == [42, 42, 5]    # failed entry keeps its ver


def test_ops_commit_fused_beyond_int32_routes_to_twin():
    from repro.core.engine.arrayheap import _UNLOCKED_WORD, _VER_SHIFT
    from repro.kernels import ops

    big = (1 << 33) + 5
    heap = np.array([1, 2, 3, big], np.int64)
    w_addr = np.array([0, 2], np.int64)
    w_val = np.array([big + 1, -7], np.int64)
    w_seg = np.zeros(2, np.int64)
    # one free write lock at a beyond-int32 version
    l_words = np.array([(big << _VER_SHIFT) | _UNLOCKED_WORD], np.int64)
    l_seg = np.zeros(1, np.int64)
    z = np.zeros((0,), np.int64)
    cv = big + 9
    new_heap, ok, new_l = ops.commit_fused(
        heap, w_addr, w_val, w_seg, l_words, l_seg,
        z, z, z, np.array([0], np.int64), np.array([big], np.int64),
        cv, 1, mode=CF.MODE_LE)
    assert ok.tolist() == [True]
    got = np.asarray(new_heap)
    assert got[0] == big + 1 and got[2] == -7 and got[3] == big
    # release word reconstructed at full width, exactly
    assert new_l.tolist() == [(cv << _VER_SHIFT) | _UNLOCKED_WORD]


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------


def _parts(groups):
    return sorted(sorted(g) for g in groups)


def test_partition_disjoint_rules():
    a = np.array([1, 2], np.int64)
    b = np.array([3, 4], np.int64)
    c = np.array([2, 5], np.int64)
    e = np.zeros((0,), np.int64)
    # fully disjoint -> one group
    assert _parts(partition_disjoint([a, b], [e, e])) == [[0, 1]]
    # write-write overlap separates
    groups = partition_disjoint([a, c], [e, e])
    assert len(groups) == 2
    # write-read overlap separates (txn 1 READS what txn 0 writes)
    groups = partition_disjoint([a, b], [e, np.array([1], np.int64)])
    assert len(groups) == 2
    # read-read sharing is harmless
    shared = np.array([9], np.int64)
    assert _parts(partition_disjoint([a, b], [shared, shared])) == [[0, 1]]
    # within-transaction duplicates are not a conflict
    dup = np.array([6, 6, 7], np.int64)
    assert _parts(partition_disjoint([dup, b], [e, e])) == [[0, 1]]


def test_partition_disjoint_sparse_indices_sort_fallback():
    # indices beyond the dense-bincount window exercise the argsort path
    hi = 1 << 40
    a = np.array([hi + 1, hi + 2], np.int64)
    b = np.array([hi + 3], np.int64)
    c = np.array([hi + 2], np.int64)
    e = np.zeros((0,), np.int64)
    assert _parts(partition_disjoint([a, b], [e, e])) == [[0, 1]]
    groups = partition_disjoint([a, c], [e, e])
    assert len(groups) == 2
    # read probe on the sparse path too
    groups = partition_disjoint([a, b], [e, np.array([hi + 1], np.int64)])
    assert len(groups) == 2


def test_partition_disjoint_three_way_split():
    a = np.array([1], np.int64)
    b = np.array([1, 2], np.int64)
    c = np.array([2, 3], np.int64)
    d = np.array([9], np.int64)
    e = np.zeros((0,), np.int64)
    groups = partition_disjoint([a, b, c, d], [e] * 4)
    got = _parts(groups)
    # a/b conflict and b/c conflict; d conflicts with nobody
    assert all(len(g) >= 1 for g in got)
    flat = sorted(i for g in got for i in g)
    assert flat == [0, 1, 2, 3]
    for g in got:
        ws = [([1], [1, 2], [2, 3], [9])[i] for i in g]
        seen = set()
        for w in ws:
            assert not (seen & set(w))
            seen |= set(w)


# ---------------------------------------------------------------------------
# engine: group == solo, one tick, degrade, individual abort
# ---------------------------------------------------------------------------

N_TXNS, WORDS = 4, 24


def _ready_batch(tm, base, stamp):
    raw = tm.raw
    txs = []
    for t in range(N_TXNS):
        tx = raw.begin(t)
        for i in range(WORDS):
            tx.write(base + t * WORDS + i, stamp + t * WORDS + i)
        txs.append(tx)
    return txs


def _heap_slice(raw, base, n):
    return np.asarray(raw.heap.gather(
        np.arange(base, base + n, dtype=np.int64)))


@pytest.mark.parametrize("backend", ["tl2", "dctl"])
def test_group_matches_solo_and_one_tick(backend):
    span = N_TXNS * WORDS
    tm_g = make_test_tm(backend, n_threads=N_TXNS, array_heap=True)
    tm_s = make_test_tm(backend, n_threads=N_TXNS, array_heap=True)
    base_g = tm_g.alloc(span)
    base_s = tm_s.alloc(span)

    txs = _ready_batch(tm_g, base_g, 1000)
    b = CommitBatcher(tm_g.raw)
    for tx in txs:
        b.add(tx)
    c0 = tm_g.raw.clock.load()
    ok = b.commit_all()
    c1 = tm_g.raw.clock.load()
    assert ok == [True] * N_TXNS
    assert b.stats["groups"] == 1 and b.stats["grouped"] == N_TXNS, b.stats
    if backend == "tl2":
        # the group invariant: ONE tick for the whole batch (solo pays
        # one per member); DCTL's deferred clock never ticks on commit
        assert c1 - c0 == 1
    else:
        assert c1 == c0

    for tx in _ready_batch(tm_s, base_s, 1000):
        tm_s.raw._try_commit(tx._ctx)
    np.testing.assert_array_equal(_heap_slice(tm_g.raw, base_g, span),
                                  _heap_slice(tm_s.raw, base_s, span))
    # serializability checker: every member's write set landed atomically
    got = _heap_slice(tm_g.raw, base_g, span)
    for t in range(N_TXNS):
        np.testing.assert_array_equal(
            got[t * WORDS:(t + 1) * WORDS],
            1000 + t * WORDS + np.arange(WORDS))
    tm_g.stop()
    tm_s.stop()


def test_overlapping_buffered_degrades_to_solo():
    tm = make_test_tm("tl2", n_threads=4, array_heap=True)
    raw = tm.raw
    base = tm.alloc(16)
    t1 = raw.begin(0)
    t2 = raw.begin(1)
    t1.write(base, 111)
    t1.write(base + 1, 1)
    t2.write(base, 222)     # same ADDRESS -> same lock word -> conflict
    t2.write(base + 2, 2)
    b = CommitBatcher(raw)
    b.add(t1)
    b.add(t2)
    ok = b.commit_all()
    # both still commit — serially, through today's solo pipeline
    assert ok == [True, True]
    assert b.stats == {"grouped": 0, "solo": 2, "groups": 0, "failed": 0}
    assert _heap_slice(raw, base, 3).tolist() == [222, 1, 2]
    tm.stop()


def test_group_member_failing_validation_aborts_alone():
    tm = make_test_tm("tl2", n_threads=4, array_heap=True)
    raw = tm.raw
    base = tm.alloc(16)
    # t0 READS base+8 then buffers a write elsewhere; a foreign commit
    # bumps base+8's version after t0's snapshot -> t0 must fail group
    # validation while its disjoint group-mates commit
    t0 = raw.begin(0)
    assert t0.read(base + 8) == 0
    t0.write(base, 7)
    bump = raw.begin(3)
    bump.write(base + 8, 55)
    raw._try_commit(bump._ctx)
    t1 = raw.begin(1)
    t1.write(base + 1, 8)
    t2 = raw.begin(2)
    t2.write(base + 2, 9)
    b = CommitBatcher(raw)
    for tx in (t0, t1, t2):
        b.add(tx)
    ok = b.commit_all()
    assert ok == [False, True, True]
    got = _heap_slice(raw, base, 9)
    assert got[0] == 0                  # failed member scattered nothing
    assert got[1] == 8 and got[2] == 9
    assert got[8] == 55
    # its write lock was never claimed: a fresh txn can take it at once
    t3 = raw.begin(0)
    t3.write(base, 77)
    raw._try_commit(t3._ctx)
    assert _heap_slice(raw, base, 1).tolist() == [77]
    tm.stop()


def test_ineligible_descriptors_fall_back_solo():
    # NOrec never opts into grouping: everything goes down today's path
    tm = make_test_tm("norec", n_threads=2, array_heap=True)
    raw = tm.raw
    base = tm.alloc(8)
    t1 = raw.begin(0)
    t1.write(base, 1)
    t2 = raw.begin(1)
    t2.write(base + 1, 2)
    b = CommitBatcher(raw)
    b.add(t1)
    b.add(t2)
    assert b.commit_all() == [True, True]
    assert b.stats["groups"] == 0 and b.stats["solo"] == 2
    assert _heap_slice(raw, base, 2).tolist() == [1, 2]
    tm.stop()


# ---------------------------------------------------------------------------
# regression: addr_lock_indices accepts generators
# ---------------------------------------------------------------------------


def test_addr_lock_indices_accepts_generator():
    tm = make_test_tm("tl2", array_heap=True)
    eng = tm.raw
    addrs = [3, 17, 255]
    want = C.addr_lock_indices(eng, np.asarray(addrs, np.int64))
    got = C.addr_lock_indices(eng, (a for a in addrs))
    np.testing.assert_array_equal(np.sort(got), np.sort(want))
    tm.stop()


# ---------------------------------------------------------------------------
# store: no per-commit host copy of the heap
# ---------------------------------------------------------------------------


class _NumpySpy:
    """Forwarding proxy for the ``numpy`` module that records the size
    of every array materialized through the patched namespace."""

    def __init__(self):
        self.max_size = 0

    def _rec(self, out):
        self.max_size = max(self.max_size, int(np.size(out)))
        return out

    def asarray(self, *a, **k):
        return self._rec(np.asarray(*a, **k))

    def array(self, *a, **k):
        return self._rec(np.array(*a, **k))

    def __getattr__(self, name):
        return getattr(np, name)


def test_mvstore_commit_keeps_heap_device_resident(monkeypatch):
    import jax

    from repro.api import mvhandle as H
    from repro.kernels import ops

    h = H.MVStoreHandle(1, start_bg=False)
    heap_len = 4096
    h.alloc(heap_len)
    spy = _NumpySpy()
    monkeypatch.setattr(H, "np", spy)

    calls = []
    real_fused = ops.commit_fused

    def spy_fused(heap, *a, **k):
        # the store hands the DEVICE buffer straight in ...
        assert isinstance(heap, jax.Array), type(heap)
        out = real_fused(heap, *a, **k)
        # ... and gets a device buffer straight back (donation path) —
        # the heap never detours through a host ndarray
        assert isinstance(out[0], jax.Array), type(out[0])
        calls.append(1)
        return out

    monkeypatch.setattr(ops, "commit_fused", spy_fused)
    for step in range(3):
        txn = h.begin(0)
        for i in range(8):
            h.write(txn._ctx, i, step * 100 + i)
        h.commit(txn)
        # the live block stays a device buffer, and the handle layer
        # never materialized a heap-sized array host-side
        assert isinstance(h.state.live["heap"], jax.Array)
        assert isinstance(h._snap[1], jax.Array)
        assert spy.max_size < heap_len, spy.max_size
    assert len(calls) == 3              # every publish took the fused path
    vals, ok = h.snapshot_bulk(np.arange(8))
    assert ok and np.asarray(vals).tolist() == [200 + i for i in range(8)]
    h.stop()


def test_mvstore_reader_losing_donation_race_aborts(monkeypatch):
    """Donation makes a stale read CRASH instead of returning stale
    data; the handle must translate that crash into the abort (inside a
    txn) or re-snapshot retry (outside) a seqlock reader would take."""
    from repro.api import mvhandle as H
    from repro.api.substrate import AbortTx

    h = H.MVStoreHandle(1, start_bg=False)
    h.alloc(16)
    txn = h.begin(0)

    boom = [RuntimeError("Array has been deleted with shape=int32[16].")]

    def raced_gather(row, a):
        if boom:
            raise boom.pop()
        return np.zeros(np.asarray(a).shape, np.int64)

    monkeypatch.setattr(h, "_gather_row", raced_gather)
    with np.testing.assert_raises(AbortTx):
        h.read_bulk(txn._ctx, range(4))
    assert not txn._ctx.active

    # outside a transaction the reader re-snapshots and retries
    boom.append(ValueError(
        "INVALID_ARGUMENT: Invalid buffer passed: buffer has been "
        "deleted or donated."))
    vals, ok = h.snapshot_bulk(range(4))
    assert ok and np.asarray(vals).shape == (4,)

    # unrelated errors still propagate untouched
    monkeypatch.setattr(
        h, "_gather_row",
        lambda row, a: (_ for _ in ()).throw(ValueError("bad addr")))
    with np.testing.assert_raises(ValueError):
        h.snapshot_bulk(range(4))
    h.stop()
