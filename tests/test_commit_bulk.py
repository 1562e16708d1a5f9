"""The batched commit pipeline (PR 5).

Five layers of assurance:

  * kernel: the ``scatter_write`` Pallas kernel agrees with its numpy
    twin (``np_write_back``) element-for-element, ragged sizes and
    beyond-int32 payloads included;
  * parity: a write set large enough to engage every bulk step
    (``try_lock_bulk`` sweep, heap scatter, ``unlock_bulk``) commits to
    exactly the state the scalar loop produces, on ALL six backends —
    including read-own-writes mid-transaction;
  * all-or-nothing: a bulk lock acquire that hits a conflict acquires
    NOTHING (no partial-hold window, no heap mutation), on both the
    commit-time (TL2) and encounter-time (DCTL) paths;
  * rollback: an encounter-time bulk write that aborts restores the
    undo log exactly and leaves no locks held;
  * normalization (the release-locks fix): two addresses colliding into
    one lock word release it exactly ONCE on commit and on rollback —
    a second per-address unlock could stomp a lock another thread had
    since claimed.
"""
import numpy as np
import pytest

from repro.api import AbortTx, make_tm, run
from repro.configs.paper_stm import MultiverseParams
from repro.core.engine import commit as C
from repro.core.engine.validation import BULK_MIN

from tests._backends import ALL_BACKENDS, WORD_BACKENDS, make_test_tm

N = BULK_MIN + 44          # comfortably past the bulk threshold


def _word_tm(backend, n_threads=2, lock_bits=10):
    return make_tm(backend, n_threads,
                   params=MultiverseParams(k1=50, k2=200, k3=200,
                                           lock_table_bits=lock_bits),
                   array_heap=True)


# ---------------------------------------------------------------------------
# kernel twin agreement (scatter_write)
# ---------------------------------------------------------------------------


def test_scatter_kernel_matches_numpy_twin():
    from repro.kernels import scatter_write as SW

    rng = np.random.default_rng(7)
    for h, n in ((64, 16), (512, 512), (1000, 128), (3000, 2000)):
        heap = rng.integers(-100, 100, size=h).astype(np.int32)
        addrs = rng.choice(h, size=n, replace=False).astype(np.int32)
        vals = rng.integers(-100, 100, size=n).astype(np.int32)
        want = SW.np_write_back(heap, addrs, vals)
        # the kernel's contract: [R, 128] rows, ascending addresses,
        # batch padded to whole tiles with an address it skips (h)
        tile = 1024
        order = np.argsort(addrs)
        pad = (-n) % tile
        a = np.pad(addrs[order], (0, pad), constant_values=h)
        v = np.pad(vals[order], (0, pad))
        rows = np.pad(heap, (0, (-h) % SW.LANES)).reshape(-1, SW.LANES)
        got = np.asarray(SW.scatter_write_flat(
            rows, a, v, n_words=h, tile=tile, interpret=True))
        np.testing.assert_array_equal(got.reshape(-1)[:h], want)


def test_ops_write_back_pads_ragged_batches():
    from repro.kernels import ops
    from repro.kernels.scatter_write import np_write_back

    rng = np.random.default_rng(13)
    heap = rng.integers(0, 100, size=300).astype(np.int64)
    for n in (1, 7, 63, 300):
        addrs = rng.choice(300, size=n, replace=False)
        vals = rng.integers(0, 100, size=n).astype(np.int64)
        got = ops.write_back(heap, addrs, vals, interpret=True)
        np.testing.assert_array_equal(got, np_write_back(heap, addrs,
                                                         vals))
    # empty batch: unchanged copy
    np.testing.assert_array_equal(
        ops.write_back(heap, np.zeros(0, np.int64), np.zeros(0, np.int64),
                       interpret=True),
        heap)


def test_ops_write_back_exact_beyond_int32():
    """Payloads past int32 must land exact (the wrapper must not let the
    x64-disabled jax path truncate them silently)."""
    from repro.kernels import ops

    big = (1 << 40) + 123
    heap = np.arange(16, dtype=np.int64)
    out = ops.write_back(heap, np.array([3, 5]),
                         np.array([big, -big], np.int64))
    assert out[3] == big and out[5] == -big
    # big values already IN the heap must survive a small-value scatter
    heap2 = np.array([big, 1, 2], np.int64)
    out2 = ops.write_back(heap2, np.array([1]), np.array([7], np.int64))
    assert out2.tolist() == [big, 7, 2]


def test_ops_write_back_rejects_beyond_int32_addresses():
    """ADDRESSES past int32 must not truncate through the kernel's int32
    cast and scatter to the wrong word (or vanish): such batches route
    to the numpy twin, where an out-of-range address raises."""
    from repro.kernels import ops

    heap = np.arange(16, dtype=np.int64)
    with pytest.raises(IndexError):
        ops.write_back(heap, np.array([(1 << 31) + 5], np.int64),
                       np.array([1], np.int64))


def test_scatter_paths_reject_negative_addresses():
    """A negative address wraps under numpy/jax fancy indexing and would
    silently overwrite (or read) a word near the end of the heap; every
    scatter/gather bulk path must raise instead, mutating nothing."""
    import jax.numpy as jnp

    from repro.core.engine.arrayheap import ArrayHeap
    from repro.kernels.scatter_write import np_write_back

    h = ArrayHeap(8)
    h.alloc(8, 5)
    with pytest.raises(IndexError):
        h.scatter(np.array([2, -1]), np.array([9, 9]))
    assert h[2] == 5 and h[7] == 5            # nothing written
    with pytest.raises(IndexError):
        h.gather(np.array([0, -3]))
    with pytest.raises(IndexError):
        np_write_back(np.zeros(8, np.int64), np.array([-3]),
                      np.array([1]))
    with pytest.raises(IndexError):
        C.scatter_row(jnp.arange(8), np.array([-1]),
                      np.array([1], np.int64))


# ---------------------------------------------------------------------------
# parity: bulk == scalar commit, all six backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_write_bulk_commits_like_scalar(backend):
    """The same rotate-a-block update, once through ``tx.write_bulk``
    (bulk lock sweep + scatter at N >= BULK_MIN) and once through the
    scalar ``tx.write`` loop: identical heap afterwards, and mid-txn
    reads see the batch's own writes."""
    def build(tm):
        base = tm.alloc(N, 0)
        run(tm, lambda tx: tx.write_bulk(range(base, base + N),
                                         list(range(N))), tid=0)
        return base

    def rotate_bulk(tm, base):
        def tx_body(tx):
            vals = np.asarray(tx.read_bulk(range(base, base + N)),
                              np.int64)
            tx.write_bulk(range(base, base + N), np.roll(vals, 1))
            # read-own-writes: the batch's values are visible mid-txn
            assert int(tx.read(base)) == N - 1
            assert int(tx.read(base + 1)) == 0
        run(tm, tx_body, tid=0)

    def rotate_scalar(tm, base):
        def tx_body(tx):
            vals = [int(v) for v in tx.read_bulk(range(base, base + N))]
            for i in range(N):
                tx.write(base + i, vals[(i - 1) % N])
        run(tm, tx_body, tid=0)

    if backend == "mvstore":
        tm_b = make_test_tm(backend, n_threads=1)
        tm_s = make_test_tm(backend, n_threads=1)
    else:
        tm_b, tm_s = _word_tm(backend), _word_tm(backend)
    try:
        base_b, base_s = build(tm_b), build(tm_s)
        rotate_bulk(tm_b, base_b)
        rotate_scalar(tm_s, base_s)
        got = [int(tm_b.peek(base_b + i)) for i in range(N)]
        want = [int(tm_s.peek(base_s + i)) for i in range(N)]
        assert got == want == [(i - 1) % N for i in range(N)]
    finally:
        tm_b.stop()
        tm_s.stop()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_write_bulk_duplicate_addresses_last_write_wins(backend):
    """``write_bulk`` promises ``for a, v: write(a, v)`` semantics, so a
    duplicated address must keep the LAST value on every backend — the
    encounter-time scatter paths collapse duplicates explicitly (a raw
    fancy-index scatter keeps an unspecified writer)."""
    tm = make_test_tm(backend, n_threads=1) if backend == "mvstore" \
        else _word_tm(backend)
    try:
        base = tm.alloc(N, 0)
        addrs = list(range(base, base + N)) + [base + 5, base + 5]
        vals = list(range(N)) + [777, 888]
        run(tm, lambda tx: tx.write_bulk(addrs, vals), tid=0)
        assert int(tm.peek(base + 5)) == 888
        assert int(tm.peek(base + 4)) == 4
    finally:
        tm.stop()


@pytest.mark.parametrize("backend", WORD_BACKENDS)
def test_write_bulk_engages_bulk_lock_path(backend):
    """At N >= BULK_MIN on the array heap, the write locks really are
    claimed (released at commit) — pinned via the lock table's held_by
    while the transaction is still open."""
    tm = _word_tm(backend)
    try:
        base = tm.alloc(N, 7)
        raw = tm.raw
        run(tm, lambda tx: tx.write(base, 7), tid=0)  # settle the clock
        tx = tm.begin(0)
        try:
            tx.write_bulk(range(base, base + N), [1] * N)
        except AbortTx:      # deferred-clock first-write abort: retry
            tm.abort(tx)
            tx = tm.begin(0)
            tx.write_bulk(range(base, base + N), [1] * N)
        if backend in ("tl2", "norec"):
            assert len(tx._ctx.write_map) == N     # buffered until commit
            assert len(raw.locks.held_by(0)) == 0
        else:
            assert len(raw.locks.held_by(0)) > 0   # encounter-time claims
            assert len(tx._ctx.undo) == N
        tm.commit(tx)
        assert len(raw.locks.held_by(0)) == 0
        assert all(int(tm.peek(base + i)) == 1 for i in range(N))
    finally:
        tm.stop()


# ---------------------------------------------------------------------------
# all-or-nothing conflict behavior
# ---------------------------------------------------------------------------


def test_bulk_acquire_all_or_nothing_on_conflict():
    """TL2 commit-time bulk acquire: when ONE lock in the batch is held
    by another thread, the sweep must acquire NOTHING and the commit
    must abort with the heap untouched."""
    tm = _word_tm("tl2")
    try:
        raw = tm.raw
        base = tm.alloc(N, 7)
        # tid 1 holds the lock covering the LAST address
        victim_idx = raw.locks.index(base + N - 1)
        st = raw.locks.read(victim_idx)
        assert raw.locks.try_lock(victim_idx, st, tid=1)
        before = [int(tm.peek(base + i)) for i in range(N)]
        with pytest.raises(AbortTx):
            with tm.txn(tid=0) as tx:
                tx.write_bulk(range(base, base + N), [9] * N)
        assert len(raw.locks.held_by(0)) == 0      # nothing acquired
        assert [int(tm.peek(base + i)) for i in range(N)] == before
        raw.locks.unlock(victim_idx)
    finally:
        tm.stop()


def test_encounter_bulk_write_conflict_aborts_clean():
    """DCTL encounter-time bulk write: a conflicting batch aborts with
    no locks held and no words written (the scalar loop would have
    locked and written a prefix, then rolled it back — same end state,
    which this pins)."""
    tm = _word_tm("dctl")
    try:
        raw = tm.raw
        base = tm.alloc(N, 7)
        victim_idx = raw.locks.index(base + N // 2)
        st = raw.locks.read(victim_idx)
        assert raw.locks.try_lock(victim_idx, st, tid=1)
        with pytest.raises(AbortTx):
            with tm.txn(tid=0) as tx:
                tx.write_bulk(range(base, base + N), [9] * N)
        assert len(raw.locks.held_by(0)) == 0
        assert all(int(tm.peek(base + i)) == 7 for i in range(N))
        raw.locks.unlock(victim_idx)
    finally:
        tm.stop()


# ---------------------------------------------------------------------------
# encounter-time bulk rollback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ("dctl", "tinystm", "multiverse"))
def test_bulk_rollback_restores_undo_exactly(backend):
    """A bulk-written batch that aborts mid-transaction must scatter the
    undo log back exactly (first-write-wins pre-images included) and
    release every lock at a bumped clock."""
    tm = _word_tm(backend)
    try:
        raw = tm.raw
        base = tm.alloc(N, 0)
        run(tm, lambda tx: tx.write_bulk(range(base, base + N),
                                         list(range(N))), tid=0)
        # bump past the setup commit's versions so the single-attempt
        # txn below cannot hit the deferred clock's first-write abort
        raw.clock.increment()
        clock0 = raw.clock.load()

        class Boom(Exception):
            pass

        with pytest.raises(Boom):
            with tm.txn(tid=0) as tx:
                # scalar write first: ITS pre-image must win over the
                # bulk batch's later gather of the already-dirty word
                tx.write(base + 3, -5)
                tx.write_bulk(range(base, base + N), [-1] * N)
                assert int(tx.read(base + 3)) == -1
                raise Boom()
        assert [int(tm.peek(base + i)) for i in range(N)] == \
            list(range(N))
        assert len(raw.locks.held_by(0)) == 0
        assert raw.clock.load() > clock0           # deferred-clock bump
    finally:
        tm.stop()


# ---------------------------------------------------------------------------
# snapshot extension: bump BEFORE revalidate (serializability)
# ---------------------------------------------------------------------------


def test_extension_bumps_clock_before_revalidating():
    """``extend_and_relock`` must advance the deferred clock FIRST and
    revalidate at the old ``r_clock`` SECOND.  The reverse order has a
    serializability hole: a foreign transaction that locks, overwrites a
    read-set address, and releases at the pre-bump clock — entirely
    between the revalidation and the bump — publishes at a version the
    extended snapshot (``r_clock = C+1``) accepts under V_LT, so the
    stale read is NEVER caught and the commit succeeds.  This test
    injects exactly that foreign commit inside ``clock.increment`` (the
    first instant of the extension under the fixed order, the unguarded
    window under the old one) and requires the transaction to abort.
    """
    tm = _word_tm("dctl")
    try:
        raw = tm.raw
        base = tm.alloc(N, 0)
        x = tm.alloc(1, 42)
        # leaves every batch word's version == the current clock, so the
        # next bulk claim is version-blocked and takes the extension
        run(tm, lambda tx: tx.write_bulk(range(base, base + N),
                                         [1] * N), tid=0)
        tx = tm.begin(0)
        assert int(tx.read(x)) == 42           # x joins the read set
        orig_inc = raw.clock.increment
        x_idx = raw.locks.index(x)

        def racing_increment():
            # foreign tid 1: lock x's word, overwrite it, release at the
            # CURRENT (pre-bump) clock — the deferred-clock publish
            raw.clock.increment = orig_inc     # fire exactly once
            st = raw.locks.read(x_idx)
            assert raw.locks.try_lock(x_idx, st, tid=1)
            raw.heap[x] = 99
            raw.locks.unlock(x_idx, raw.clock.load())
            return orig_inc()

        raw.clock.increment = racing_increment
        try:
            with pytest.raises(AbortTx):
                tx.write_bulk(range(base, base + N), [2] * N)
                tm.commit(tx)
            tm.abort(tx)
        finally:
            raw.clock.increment = orig_inc
        # the foreign write survives; the doomed batch wrote nothing
        assert int(tm.peek(x)) == 99
        assert all(int(tm.peek(base + i)) == 1 for i in range(N))
        assert len(raw.locks.held_by(0)) == 0
    finally:
        tm.stop()


@pytest.mark.parametrize("backend", ("dctl", "tinystm", "multiverse"))
def test_scalar_write_extends_past_own_commit(backend):
    """Back-to-back SCALAR write transactions must not abort on their own
    previous commit.  Under the deferred clock a commit leaves its lock
    words at version == the current clock, so the next transaction's
    encounter-time validate (``version < r_clock``) fails with nothing
    conflicting; the scalar path used to eat one abort-and-replay per
    commit where the bulk path snapshot-extends.  Single-attempt
    transactions (no retry loop) pin that the extension now serves the
    scalar path too — any abort surfaces as an uncaught AbortTx."""
    tm = _word_tm(backend)
    try:
        raw = tm.raw
        a = tm.alloc(1, 0)
        b = tm.alloc(1, 0)
        for k, addr in enumerate((a, b, a), start=1):
            tx = tm.begin(0)
            tx.write(addr, k)               # must not raise AbortTx
            tm.commit(tx)
        assert int(tm.peek(a)) == 3
        assert int(tm.peek(b)) == 2
        assert len(raw.locks.held_by(0)) == 0
    finally:
        tm.stop()


def test_scalar_extension_bumps_clock_before_revalidating():
    """Scalar twin of ``test_extension_bumps_clock_before_revalidating``:
    ``extend_snapshot`` must advance the deferred clock FIRST and
    revalidate at the old ``r_clock`` SECOND, for exactly the bulk
    path's reason — a foreign commit landing entirely between a
    revalidate-then-bump pair publishes at the pre-bump clock, which
    the extended snapshot then accepts as valid forever.  The foreign
    commit is injected inside ``clock.increment`` (the first instant of
    the extension under the fixed order) and must force an abort."""
    tm = _word_tm("dctl")
    try:
        raw = tm.raw
        w = tm.alloc(1, 0)
        x = tm.alloc(1, 42)
        # distinct lock words, so w's claim cannot see x's foreign lock
        assert raw.locks.index(w) != raw.locks.index(x)
        # leaves w's version == the current clock, so the next scalar
        # write is version-blocked and takes the extension
        run(tm, lambda tx: tx.write(w, 1), tid=0)
        tx = tm.begin(0)
        assert int(tx.read(x)) == 42           # x joins the read set
        orig_inc = raw.clock.increment
        x_idx = raw.locks.index(x)

        def racing_increment():
            # foreign tid 1: lock x's word, overwrite it, release at the
            # CURRENT (pre-bump) clock — the deferred-clock publish
            raw.clock.increment = orig_inc     # fire exactly once
            st = raw.locks.read(x_idx)
            assert raw.locks.try_lock(x_idx, st, tid=1)
            raw.heap[x] = 99
            raw.locks.unlock(x_idx, raw.clock.load())
            return orig_inc()

        raw.clock.increment = racing_increment
        try:
            with pytest.raises(AbortTx):
                tx.write(w, 2)
                tm.commit(tx)
            tm.abort(tx)
        finally:
            raw.clock.increment = orig_inc
        # the foreign write survives; the doomed write landed nothing
        assert int(tm.peek(x)) == 99
        assert int(tm.peek(w)) == 1
        assert len(raw.locks.held_by(0)) == 0
    finally:
        tm.stop()


# ---------------------------------------------------------------------------
# lock-index normalization (the release_locks fix)
# ---------------------------------------------------------------------------


def _colliding_addrs(locks, base, n, count=2):
    """Find `count` addresses in [base, base+n) sharing one lock index."""
    seen = {}
    for a in range(base, base + n):
        idx = locks.index(a)
        seen.setdefault(idx, []).append(a)
        if len(seen[idx]) >= count:
            return idx, seen[idx][:count]
    raise AssertionError("no collision found — shrink the lock table")


@pytest.mark.parametrize("backend", ("multiverse", "dctl"))
@pytest.mark.parametrize("path", ("commit", "rollback"))
def test_colliding_addresses_release_once(backend, path):
    """Two addresses sharing a lock word must release it exactly once on
    commit AND on rollback.  Releasing per heap address used to unlock
    the shared word twice; after the first release another thread can
    legitimately claim it, and the second release stomps their lock."""
    tm = _word_tm(backend, lock_bits=4)    # 16 words: collisions certain
    try:
        raw = tm.raw
        base = tm.alloc(64, 7)
        # versions start at the clock: bump so a single-attempt txn
        # cannot hit the deferred clock's first-write abort
        raw.clock.increment()
        idx, (a1, a2) = _colliding_addrs(raw.locks, base, 64)
        released = []
        orig_unlock = raw.locks.unlock
        orig_bulk = raw.locks.unlock_bulk

        def counting_unlock(i, version=None):
            released.append(int(i))
            orig_unlock(i, version)

        def counting_bulk(idxs, version=None):
            released.extend(int(i) for i in np.asarray(idxs))
            orig_bulk(idxs, version)

        raw.locks.unlock = counting_unlock
        raw.locks.unlock_bulk = counting_bulk
        try:
            if path == "commit":
                run(tm, lambda tx: (tx.write(a1, 1), tx.write(a2, 2)),
                    tid=0, max_retries=50)
            else:
                with pytest.raises(AbortTx):
                    with tm.txn(tid=0) as tx:
                        tx.write(a1, 1)
                        tx.write(a2, 2)
                        raise AbortTx()
        finally:
            raw.locks.unlock = orig_unlock
            raw.locks.unlock_bulk = orig_bulk
        # the colliding word was released exactly once per release pass
        # (retries each release once; never twice back-to-back)
        assert released.count(idx) >= 1
        for i in range(len(released) - 1):
            assert not (released[i] == idx and released[i + 1] == idx), \
                "shared lock word released twice in one pass"
        st = raw.locks.read(idx)
        assert not st.locked
    finally:
        tm.stop()


def test_publish_bulk_matches_scalar_publish():
    """PackedVLT.publish_bulk == a loop of scalar publishes: same rows,
    same seqlocks even, same select results."""
    from repro.core.vlt import PackedVLT, VListNode

    def seeded():
        m = PackedVLT(32, depth=3)
        for b, a, v in ((1, 10, 100), (1, 11, 110), (9, 20, 200)):
            m.seed(b, a, VListNode(None, 1, v, False))
        return m

    buckets = np.array([1, 1, 9, 5])
    addrs = np.array([10, 11, 20, 99])
    datas = [101, 111, 201, 5]
    m_bulk, m_scalar = seeded(), seeded()
    m_bulk.publish_bulk(buckets, addrs, 7, datas)
    for b, a, v in zip(buckets, addrs, datas):
        m_scalar.publish(int(b), int(a), 7, v)
    np.testing.assert_array_equal(m_bulk._ts, m_scalar._ts)
    np.testing.assert_array_equal(m_bulk._data, m_scalar._data)
    np.testing.assert_array_equal(m_bulk._addr, m_scalar._addr)
    assert (m_bulk._seq % 2 == 0).all()
    q_idx = np.array([1, 1, 9])
    q_addr = np.array([10, 11, 20])
    for clock, want in ((100, [101, 111, 201]), (7, [100, 110, 200])):
        vb, okb = m_bulk.select(q_idx, q_addr, clock)
        vs, oks = m_scalar.select(q_idx, q_addr, clock)
        assert okb.tolist() == oks.tolist() == [True] * 3
        assert vb.tolist() == vs.tolist() == want
