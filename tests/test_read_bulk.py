"""`Txn.read_bulk` / `snapshot_bulk`: batched reads on every backend.

Three layers of assurance:

  * unit: batch == scalar loop on quiescent heaps (values, read-own-
    writes, read-count accounting, empty/duplicate batches), fallback on
    foreign-locked words, and the deterministic versioned-snapshot case
    (a bulk read returns the PAST value of a word committed after the
    reader's snapshot);
  * kernel: the Pallas gather twin agrees with the numpy fancy-index
    element-for-element, ragged sizes included;
  * concurrency (the snapshot-consistency satellite): scanner threads
    `read_bulk` the whole region while updaters commit balance-preserving
    transfers — every completed scan must observe an exact region sum,
    on the word backends and on mvstore.
"""
import random
import sys
import threading
import time

import numpy as np
import pytest

from repro.api import AbortTx, MaxRetriesExceeded, run

from tests._backends import ALL_BACKENDS, WORD_BACKENDS, make_test_tm

INITIAL = 10


# ---------------------------------------------------------------------------
# unit: batch == scalar loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_read_bulk_matches_scalar(backend):
    tm = make_test_tm(backend, n_threads=1)
    base = tm.alloc(300, 7)
    def body(tx):
        bulk = [int(v) for v in tx.read_bulk(range(base, base + 300))]
        scalar = [int(tx.read(base + i)) for i in range(300)]
        return bulk, scalar
    bulk, scalar = run(tm, body, tid=0)
    assert bulk == scalar == [7] * 300
    tm.stop()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("array_heap", [False, True])
def test_read_bulk_sees_own_writes(backend, array_heap):
    if backend == "mvstore" and array_heap:
        pytest.skip("store layer is always array-backed")
    kw = {} if backend == "mvstore" else {"array_heap": array_heap}
    tm = make_test_tm(backend, n_threads=1, **kw)
    base = tm.alloc(64, 1)
    def body(tx):
        tx.write(base + 3, 42)
        tx.write(base + 60, 43)
        return [int(v) for v in tx.read_bulk(
            [base + 2, base + 3, base + 60, base + 3])]
    assert run(tm, body, tid=0) == [1, 42, 43, 42]
    tm.stop()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_read_bulk_counts_reads_and_handles_empty(backend):
    tm = make_test_tm(backend, n_threads=1)
    base = tm.alloc(128, 0)
    def body(tx):
        assert list(tx.read_bulk([])) == []
        tx.read_bulk(range(base, base + 128))
        return tx.read_count
    assert run(tm, body, tid=0) >= 128
    tm.stop()


def test_read_bulk_scalar_fallback_aborts_on_foreign_lock():
    """A word encounter-locked by another thread fails the vectorized
    predicate; the per-element scalar fallback must then abort with the
    policy's exact semantics (not return a torn value)."""
    tm = make_test_tm("dctl", n_threads=2)
    base = tm.alloc(400, 5)
    tx0 = None
    for _ in range(3):                # deferred clock: first access may
        tx0 = tm.begin(0)             # abort once on a fresh TM
        try:
            tx0.write(base + 17, 99)  # encounter-time: lock held, in-place
            break
        except AbortTx:
            tx0 = None
    assert tx0 is not None
    with pytest.raises(MaxRetriesExceeded):
        run(tm, lambda tx: tx.read_bulk(range(base, base + 400)),
            tid=1, max_retries=3)
    tm.abort(tx0)                     # rolls the 99 back
    vals = run(tm, lambda tx: tx.read_bulk(range(base, base + 400)), tid=1)
    assert [int(v) for v in vals] == [5] * 400
    tm.stop()


def test_versioned_bulk_read_returns_snapshot_past():
    """Deterministic snapshot isolation through the hybrid bulk path: a
    versioned reader whose snapshot predates a committed write must get
    the OLD value from the version list while the heap already holds the
    new one — the paper's long-running read, in one batch."""
    tm = make_test_tm("multiverse", n_threads=2, start_bg=False)
    base = tm.alloc(300, 7)
    target = base + 5
    # warm the deferred clock past 0 (a fresh TM's first access aborts
    # once; versioning needs lock versions strictly below the snapshot)
    run(tm, lambda t: t.write(base + 299, 7), tid=0)
    # seed a version list for the target (a versioned read versions it)
    tx = tm.begin(1)
    tx._ctx.versioned = True
    assert tx.read(target) == 7
    tm.commit(tx)
    # bump the deferred clock (what any abort does) so the reader's
    # snapshot sits strictly ABOVE every version committed so far
    tm.clock.increment()
    # reader pins its snapshot, THEN a writer commits a new value
    tx = tm.begin(1)
    tx._ctx.versioned = True
    run(tm, lambda t: t.write(target, 99), tid=0)
    assert tm.peek(target) == 99
    # scan everything except UNVERSIONED words sharing the target's lock
    # bucket: their bucket version now equals the snapshot, so a Mode-Q
    # versioned reader would (correctly) abort on versioning them — the
    # scalar path included; excluding them keeps the test deterministic
    idx_t = tm.locks.index(target)
    addrs = [a for a in range(base, base + 300)
             if a == target or tm.locks.index(a) != idx_t]
    vals = tx.read_bulk(addrs)
    tm.commit(tx)
    assert int(vals[addrs.index(target)]) == 7   # the snapshot's past
    assert sum(int(v) for v in vals) == len(addrs) * 7
    assert tm.stats()["versioned_commits"] >= 1
    tm.stop()


def test_mvstore_snapshot_bulk_serves_past_clock():
    tm = make_test_tm("mvstore", n_threads=2)
    base = tm.alloc(40, 3)
    # version the block (a K1-promoted reader would do this), then commit
    tx = tm.begin(1)
    tx._ctx.versioned = True
    old = [int(v) for v in tx.read_bulk(range(base, base + 40))]
    tm.commit(tx)
    clock0 = tm.clock
    run(tm, lambda t: t.write(base + 1, 77), tid=0)
    vals, ok = tm.snapshot_bulk(range(base, base + 40))
    assert ok and int(vals[1]) == 77            # current clock: live block
    stale, ok = tm.snapshot_bulk(range(base, base + 40),
                                 read_clock=clock0)
    assert ok and [int(v) for v in stale] == old == [3] * 40
    tm.stop()


# ---------------------------------------------------------------------------
# kernel twin agreement
# ---------------------------------------------------------------------------


def test_gather_kernel_matches_numpy_twin():
    import jax.numpy as jnp
    from repro.kernels import gather_read
    rng = np.random.default_rng(0)
    heap = jnp.asarray(rng.integers(0, 1 << 20, size=2048), jnp.int32)
    for n in (1024, 3072):
        addrs = jnp.asarray(rng.integers(0, 2048, size=n), jnp.int32)
        out = gather_read.gather_read_flat(
            heap.reshape(-1, gather_read.LANES), addrs, tile=1024,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out).reshape(-1),
                                      np.asarray(heap)[np.asarray(addrs)])


def test_ops_snapshot_read_pads_ragged_batches():
    import jax.numpy as jnp
    from repro.kernels import ops
    heap = jnp.arange(1000, dtype=jnp.int32)
    for n in (1, 7, 130, 777):
        addrs = np.arange(n) * 3 % 1000
        out = np.asarray(ops.snapshot_read(heap, addrs, interpret=True))
        assert out.shape == (n,)
        np.testing.assert_array_equal(out, np.arange(1000)[addrs])


_SCATTER = np.random.default_rng(3).integers(0, 5000, 1024)

#: name -> (heap words, addresses, block steps, row steps); every batch
#: is gathered in 1024-address steps
_PATH_CASES = {
    "row_aligned_run": (7048, np.arange(1024, 3072), 2, 0),
    "unaligned_run": (7048, np.arange(37, 2085), 2, 0),
    # 5000 + n words: the run ends in the last row, which is partial
    "run_to_ragged_heap_end": (7048, np.arange(5000, 7048), 2, 0),
    # an aligned run up to the last row of a whole-row heap
    "aligned_run_to_heap_end": (4096, np.arange(2048, 4096), 2, 0),
    # the second step holds 76 run words and 948 PAD_ADDR pads
    "run_then_padding": (7048, np.arange(300, 1400), 1, 1),
    "run_then_scattered": (7048, np.concatenate(
        [np.arange(4000, 5024), _SCATTER]), 1, 1),
    "run_turns_scattered_mid_step": (7048, np.concatenate(
        [np.arange(10, 1510), _SCATTER[:548]]), 1, 1),
    "stride_2": (7048, np.arange(0, 4096, 2), 0, 2),
    "descending": (7048, np.arange(3000, 952, -1), 0, 2),
    "repeated": (7048, np.full(1024, 777), 0, 1),
    "one_step_run": (7048, np.arange(129, 1153), 1, 0),
    "one_address": (7048, np.array([4321]), 0, 1),
}


@pytest.mark.parametrize("case", sorted(_PATH_CASES))
def test_snapshot_read_block_and_row_paths(case):
    """A step of consecutive addresses takes the one-copy block path,
    any other step the per-address row path; both equal ``heap[a]``,
    and ``COUNTS.tiles`` counts the steps by the path they took."""
    from repro.kernels import ops
    words, a, block, row = _PATH_CASES[case]
    heap = np.random.default_rng(words).integers(
        -(1 << 30), 1 << 30, words).astype(np.int32)
    ops.COUNTS.reset()
    out = ops.snapshot_read(heap, a, interpret=True)
    np.testing.assert_array_equal(out, heap[a])
    assert ops.COUNTS.tiles == {"gather_block_tiles": block,
                                "gather_row_tiles": row}
    ops.COUNTS.reset()


# ---------------------------------------------------------------------------
# concurrency: balance-preserving snapshots (the satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_scanner_snapshots_are_balance_preserving(backend):
    """Scanner `read_bulk`s the whole region while updaters commit
    transfers; EVERY completed scan must see the exact region sum.  The
    updaters then stop and the scanner must still complete cleanly (so
    the test is deterministic about completing, while any torn batch
    during the concurrent phase would have tripped the assertion)."""
    n = 128
    n_threads = 3
    kw = {"array_heap": True} if backend in WORD_BACKENDS else {}
    tm = make_test_tm(backend, n_threads=n_threads, **kw)
    base = tm.alloc(n, INITIAL)
    expected = n * INITIAL
    stop = threading.Event()
    scans = {"done": 0, "bad": 0}

    def updater(tid):
        r = random.Random(1000 + tid)
        def transfer(tx):
            i = r.randrange(n)
            j = (i + 1 + r.randrange(n - 1)) % n
            tx.write(base + i, int(tx.read(base + i)) - 1)
            tx.write(base + j, int(tx.read(base + j)) + 1)
        while not stop.is_set():
            try:
                run(tm, transfer, tid=tid, max_retries=2000)
            except MaxRetriesExceeded:
                pass

    def scan_once(max_retries):
        def scan(tx):
            total = 0
            for off in range(0, n, 64):
                total += int(np.sum(np.asarray(
                    tx.read_bulk(range(base + off, base + off + 64)),
                    dtype=np.int64)))
            return total
        total = run(tm, scan, tid=n_threads - 1,
                    max_retries=max_retries)
        scans["done"] += 1
        if total != expected:
            scans["bad"] += 1

    old_si = sys.getswitchinterval()
    sys.setswitchinterval(2e-5)
    threads = [threading.Thread(target=updater, args=(t,), daemon=True)
               for t in range(2)]
    try:
        [t.start() for t in threads]
        deadline = time.time() + 2.0
        while time.time() < deadline and scans["done"] < 5:
            try:
                scan_once(max_retries=10)
            except MaxRetriesExceeded:
                pass                   # unversioned TMs starve here
    finally:
        stop.set()
        [t.join() for t in threads]
        sys.setswitchinterval(old_si)
    scan_once(max_retries=100)         # quiescent: must complete exactly
    assert scans["bad"] == 0
    assert scans["done"] >= 1
    tm.stop()
