"""Frontier-at-a-time traversal + packed-VLT version gather.

Four layers of assurance:

  * unit: ``traverse_bulk`` preserves DFS emission order, honors
    ``limit``, threads per-item state, and never touches the Python
    stack for depth (a degenerate tree deeper than the recursion limit
    traverses fine);
  * parity (the batch-vs-scalar satellite): ``extbst.range_query`` and
    chained ``HashMap.size_query`` match hand-rolled scalar traversals
    on ALL six backends;
  * kernel: the ``version_select`` Pallas kernel agrees with the numpy
    twin (``core.vlt.np_version_select``) element-for-element, ragged
    sizes included;
  * mirror: a versioned bulk read resolves a recently-written word's
    snapshot past through ``PackedVLT.select`` (one gather, no scalar
    version-list walk), and rows the mirror cannot represent (colliding
    buckets, non-int payloads) fail closed to the scalar fallback.
"""
import random
import sys

import numpy as np
import pytest

from repro.api import run
from repro.core.engine.traverse import chase_bulk, traverse_bulk
from repro.core.vlt import (
    EMPTY_TS,
    PackedVLT,
    VListNode,
    np_version_select,
)
from repro.structs import ExternalBST, HashMap

from tests._backends import ALL_BACKENDS, make_test_tm


# ---------------------------------------------------------------------------
# unit: ordering, limit, state, depth
# ---------------------------------------------------------------------------


def test_traverse_bulk_preserves_dfs_order_and_limit():
    """A hand-built binary tree on the raw heap: emission must be exactly
    the in-order walk, and ``limit`` must truncate it mid-traversal."""
    tm = make_test_tm("tl2", n_threads=1)
    tm.alloc(1)                              # burn address 0 (NULL)
    # node layout: [0]=value, [1]=left, [2]=right (0 = null)
    def node(v, l=0, r=0):
        base = tm.alloc(3, 0)
        tm.run(lambda tx: (tx.write(base, v), tx.write(base + 1, l),
                           tx.write(base + 2, r)))
        return base
    #        4
    #      2   6
    #     1 3 5 7
    n1, n3, n5, n7 = node(1), node(3), node(5), node(7)
    n2, n6 = node(2, n1, n3), node(6, n5, n7)
    n4 = node(4, n2, n6)

    def expand(state, w, emit, push):
        if int(w[1]):
            push(w[1], 3, state + 1)
        emit((int(w[0]), state))
        if int(w[2]):
            push(w[2], 3, state + 1)

    out = run(tm, lambda tx: traverse_bulk(tx, [(n4, 3, 0)], expand))
    assert [v for v, _ in out] == [1, 2, 3, 4, 5, 6, 7]
    assert [d for _, d in out] == [2, 1, 2, 0, 2, 1, 2]   # depth state
    # NOTE the emit-between-pushes above is in-order traversal; limit
    # stops at the resolved prefix, never emitting out of order
    out = run(tm, lambda tx: traverse_bulk(tx, [(n4, 3, 0)], expand,
                                           limit=4))
    assert [v for v, _ in out] == [1, 2, 3, 4]
    tm.stop()


def test_chase_bulk_counts_rounds():
    tm = make_test_tm("tl2", n_threads=1)
    tm.alloc(1)                              # burn address 0 (NULL)
    # three chains of length 1, 3, 5 — cells: [0]=next
    def chain(n):
        addrs = [tm.alloc(1, 0) for _ in range(n)]
        for a, b in zip(addrs, addrs[1:]):
            tm.run(lambda tx, a=a, b=b: tx.write(a, b))
        return addrs[0]
    heads = [chain(1), chain(3), chain(5)]
    seen = []

    def advance(cur, vals):
        seen.append(cur.size)
        nxt = np.asarray(vals, np.int64)
        return nxt[nxt != 0]

    rounds = run(tm, lambda tx: chase_bulk(tx, heads, advance))
    assert rounds == 5                       # longest chain
    assert seen == [3, 2, 2, 1, 1]           # lockstep attrition
    tm.stop()


def test_extbst_range_query_survives_depth_past_recursion_limit():
    """Sorted inserts build a degenerate (linked-list) BST; the iterative
    frontier walk must traverse deeper than the Python recursion limit
    allows (the old recursive DFS could not)."""
    tm = make_test_tm("tl2", n_threads=1)
    s = ExternalBST(tm)
    n = 300
    for k in range(n):
        run(tm, lambda tx, k=k: s.insert(tx, k, -k), tid=0)

    def stack_depth():
        f, d = sys._getframe(), 0
        while f:
            d += 1
            f = f.f_back
        return d

    old = sys.getrecursionlimit()
    # leave ~150 frames of headroom — far less than the tree's ~300
    # levels, so a recursive walk would blow the stack here
    sys.setrecursionlimit(stack_depth() + 150)
    try:
        out = run(tm, lambda tx: s.range_query(tx, 0, n), tid=0)
    finally:
        sys.setrecursionlimit(old)
    assert [int(k) for k, _ in out] == list(range(n))
    tm.stop()


def test_traversal_readset_dedup_across_rounds():
    """Repeated frontier visits must not inflate the read set: a second
    walk of the same chain re-proves the same (idx, version) pairs and
    appends NOTHING, while a plain read_bulk outside the traversal
    keeps the historical append-always behavior (flag restored)."""
    tm = make_test_tm("tl2", n_threads=1)
    tm.alloc(1)                              # burn address 0 (NULL)
    addrs = [tm.alloc(1, 0) for _ in range(5)]
    for a, b in zip(addrs, addrs[1:]):
        run(tm, lambda tx, a=a, b=b: tx.write(a, b))
    head = addrs[0]

    def advance(cur, vals):
        nxt = np.asarray(vals, np.int64)
        return nxt[nxt != 0]

    def body(tx):
        d = tx._ctx
        chase_bulk(tx, [head], advance)
        n1 = len(d.read_set)
        assert n1 > 0
        chase_bulk(tx, [head], advance)      # SAME chain again
        assert len(d.read_set) == n1         # deduped across rounds
        # traverse_bulk dedups too (same walk, span-1 items)
        out = traverse_bulk(
            tx, [(head, 1)],
            lambda s, w, emit, push: (emit(int(w[0])),
                                      push(int(w[0]), 1)
                                      if int(w[0]) else None))
        assert len(out) == 5
        assert len(d.read_set) == n1
        assert not d.dedup_read_set          # flag restored on exit
        tx.read_bulk([head])                 # plain batch: appends again
        assert len(d.read_set) == n1 + 1
    run(tm, body)
    tm.stop()


# ---------------------------------------------------------------------------
# parity: batch traversal == scalar traversal, all six backends
# ---------------------------------------------------------------------------


def _scalar_bst_range(s, tx, lo, count):
    """The pre-traversal-layer recursive DFS, as the parity oracle."""
    out = []
    root = tx.read(s.root_ptr)
    if root == 0:
        return out

    def dfs(node):
        if tx.read(node):
            k = tx.read(node + 1)
            if k >= lo:
                out.append((int(k), int(tx.read(node + 4))))
                if len(out) >= count:
                    return True
            return False
        if lo < tx.read(node + 1):
            if dfs(tx.read(node + 2)):
                return True
        return dfs(tx.read(node + 3))

    dfs(root)
    return out


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_extbst_range_query_batch_matches_scalar(backend):
    tm = make_test_tm(backend, n_threads=1)
    s = ExternalBST(tm)
    keys = random.Random(5).sample(range(5000), 140)
    for k in keys:
        run(tm, lambda tx, k=k: s.insert(tx, k, k * 2), tid=0)
    for lo, count in ((0, 1000), (2500, 40), (4999, 5), (6000, 10)):
        batch = run(tm, lambda tx: s.range_query(tx, lo, count), tid=0)
        scalar = run(tm, lambda tx: _scalar_bst_range(s, tx, lo, count),
                     tid=0)
        assert [(int(k), int(v)) for k, v in batch] == scalar
    tm.stop()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_hashmap_size_query_batch_matches_scalar(backend):
    """16 buckets x 120 keys -> every bucket chains several nodes deep,
    so the lockstep chain chase is genuinely exercised per backend."""
    tm = make_test_tm(backend, n_threads=1)
    h = HashMap(tm, n_buckets=16)
    keys = random.Random(9).sample(range(10000), 120)
    for k in keys:
        run(tm, lambda tx, k=k: h.insert(tx, k, k), tid=0)

    def scalar_size(tx):
        total = 0
        for b in range(h.n_buckets):
            node = int(tx.read(h.table + b))
            while node:
                total += 1
                node = int(tx.read(node + 2))
        return total

    assert run(tm, h.size_query, tid=0) == \
        run(tm, scalar_size, tid=0) == len(keys)
    # after deletions the chains shorten mid-list; parity must hold
    for k in keys[::3]:
        run(tm, lambda tx, k=k: h.delete(tx, k), tid=0)
    assert run(tm, h.size_query, tid=0) == \
        run(tm, scalar_size, tid=0) == len(keys) - len(keys[::3])
    tm.stop()


# ---------------------------------------------------------------------------
# kernel twin agreement (version_select)
# ---------------------------------------------------------------------------


def test_version_select_kernel_matches_numpy_twin():
    import jax.numpy as jnp

    from repro.kernels import version_select as VS

    rng = np.random.default_rng(3)
    for n in (1, 7, 130, 512):
        ts = rng.integers(0, 1000, size=(n, 4)).astype(np.int64)
        ts[rng.random((n, 4)) < 0.3] = EMPTY_TS
        data = rng.integers(-5000, 5000, size=(n, 4)).astype(np.int64)
        for clock in (1, 500, 999):
            want_v, want_ok = np_version_select(ts, data, clock)
            rel = np.clip(ts - clock, -(1 << 31) + 1, (1 << 31) - 1)
            # the kernel's contract: slot-major [D, N / 128, 128],
            # ragged batches padded to a whole 1024-row tile
            tile = 1024
            pad = (-n) % tile
            relj = jnp.pad(jnp.asarray(rel, jnp.int32), ((0, pad), (0, 0)),
                           constant_values=VS.PAD_TS)
            dj = jnp.pad(jnp.asarray(data, jnp.int32), ((0, pad), (0, 0)))

            def slots(x):
                return x.T.reshape(x.shape[1], -1, VS.LANES)

            got_v, got_ok = VS.version_select_flat(
                slots(relj), slots(dj), 0, tile=tile, interpret=True)
            got_v = np.asarray(got_v).reshape(-1)[:n]
            got_ok = np.asarray(got_ok).reshape(-1)[:n] != 0
            np.testing.assert_array_equal(want_ok, got_ok)
            np.testing.assert_array_equal(want_v[want_ok], got_v[got_ok])


def test_ops_version_select_pads_ragged_batches():
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    for n in (1, 7, 63, 300):
        ts = rng.integers(0, 100, size=(n, 4)).astype(np.int64)
        data = rng.integers(0, 100, size=(n, 4)).astype(np.int64)
        vals, ok = ops.version_select(ts, data, 50, interpret=True)
        want_v, want_ok = np_version_select(ts, data, 50)
        np.testing.assert_array_equal(ok, want_ok)
        np.testing.assert_array_equal(vals[ok], want_v[want_ok])


def test_ops_version_select_exact_beyond_int32():
    """Payloads past int32 must come back exact (the wrapper must not
    let the x64-disabled jax path truncate them silently)."""
    from repro.kernels import ops

    big = (1 << 40) + 123
    ts = np.array([[5, 3], [9, 1]], np.int64)
    data = np.array([[big, 7], [-big, 8]], np.int64)
    vals, ok = ops.version_select(ts, data, 6)
    assert ok.tolist() == [True, True]
    assert vals.tolist() == [big, 8]      # row1: ts=9 rejected -> 8


# ---------------------------------------------------------------------------
# packed VLT mirror
# ---------------------------------------------------------------------------


def test_packed_vlt_select_fails_closed():
    """Way overflow, non-int payloads and torn rows must all fail select
    (-> scalar fallback), never return a wrong value; a single bucket
    collision is now SERVED by the second way (counted in way_hits)."""
    m = PackedVLT(8, depth=2)
    m.seed(3, 100, VListNode(None, 5, 42, False))
    vals, ok = m.select(np.array([3]), np.array([100]), 10)
    assert ok.tolist() == [True] and int(vals[0]) == 42
    # deeper than the mirror: version history beyond `depth` drops off
    m.publish(3, 100, 7, 43)
    m.publish(3, 100, 9, 44)
    vals, ok = m.select(np.array([3]), np.array([100]), 6)   # needs ts=5
    assert ok.tolist() == [False]
    vals, ok = m.select(np.array([3]), np.array([100]), 8)   # ts=7 -> 43
    assert ok.tolist() == [True] and int(vals[0]) == 43
    # a second address colliding into the bucket claims way 2: BOTH stay
    # vectorizable (the 2-way satellite), and the stat counts the hit
    m.seed(3, 200, VListNode(None, 6, 1, False))
    vals, ok = m.select(np.array([3, 3]), np.array([100, 200]), 100)
    assert ok.tolist() == [True, True]
    assert vals.tolist() == [44, 1]
    assert m.way_hits[1] == 1
    # publishes keep routing to the right way
    m.publish(3, 200, 12, 2)
    vals, ok = m.select(np.array([3]), np.array([200]), 100)
    assert ok.tolist() == [True] and int(vals[0]) == 2
    assert m.way_hits[1] == 2
    # a THIRD collider overflows both ways: unmirrored -> fail closed
    m.seed(3, 300, VListNode(None, 6, 9, False))
    _, ok = m.select(np.array([3]), np.array([300]), 100)
    assert ok.tolist() == [False]
    for addr, want in ((100, 44), (200, 2)):     # existing ways untouched
        vals, ok = m.select(np.array([3]), np.array([addr]), 100)
        assert ok.tolist() == [True] and int(vals[0]) == want
    # non-int payload poisons its way at publish time
    m.seed(4, 300, VListNode(None, 2, 7, False))
    m.publish(4, 300, 6, "not-an-int")
    _, ok = m.select(np.array([4]), np.array([300]), 100)
    assert ok.tolist() == [False]
    # torn row (odd seqlock) fails stability
    m.seed(5, 400, VListNode(None, 2, 9, False))
    m._seq[5] += 1
    _, ok = m.select(np.array([5]), np.array([400]), 100)
    assert ok.tolist() == [False]


def test_versioned_bulk_read_resolves_past_via_mirror():
    """The deterministic snapshot-past scenario of test_read_bulk, now
    asserting the RECENTLY-WRITTEN word resolves through the packed-VLT
    gather (one vectorized select) rather than the scalar version-list
    walk."""
    tm = make_test_tm("multiverse", n_threads=2, start_bg=False)
    base = tm.alloc(300, 7)
    target = base + 5
    run(tm, lambda t: t.write(base + 299, 7), tid=0)   # warm the clock
    tx = tm.begin(1)
    tx._ctx.versioned = True                 # seed the version list
    assert tx.read(target) == 7
    tm.commit(tx)
    tm.clock.increment()
    tx = tm.begin(1)
    tx._ctx.versioned = True                 # snapshot BEFORE the write
    run(tm, lambda t: t.write(target, 99), tid=0)
    assert tm.peek(target) == 99
    idx_t = tm.locks.index(target)
    addrs = [a for a in range(base, base + 300)
             if a == target or tm.locks.index(a) != idx_t]
    hits0 = tm.raw.policy.stats_version_gather_hits
    vals = tx.read_bulk(addrs)
    tm.commit(tx)
    assert int(vals[addrs.index(target)]) == 7        # the snapshot past
    assert tm.raw.policy.stats_version_gather_hits == hits0 + 1
    assert tm.raw.stats()["version_gather_hits"] >= 1
    tm.stop()


def test_mirror_lock_gate_defers_in_flight_commits_to_scalar():
    """While a writer HOLDS the address lock (its commit could still
    publish below a reader's snapshot), the mirror must refuse to serve
    the address — the scalar traverse owns that window.  The bulk read
    must still return the committed snapshot value, just not via the
    mirror (hits counter unchanged)."""
    from repro.api import AbortTx

    tm = make_test_tm("multiverse", n_threads=2, start_bg=False)
    base = tm.alloc(64, 7)
    target = base + 3
    run(tm, lambda t: t.write(base + 63, 7), tid=0)    # warm the clock
    tx = tm.begin(1)
    tx._ctx.versioned = True                 # seed the version list
    assert tx.read(target) == 7
    tm.commit(tx)
    # writer tid 0: encounter-locks target with an uncommitted TBD write
    wtx = None
    for _ in range(3):                       # deferred clock may abort once
        wtx = tm.begin(0)
        try:
            wtx.write(target, 99)
            break
        except AbortTx:
            wtx = None
    assert wtx is not None
    # versioned reader: its snapshot is at/below the writer's, so the
    # pending TBD is correctly skippable and the read must return 7 —
    # through the SCALAR traverse, because the lock gate excludes the
    # locked address from the mirror
    rtx = tm.begin(1)
    rtx._ctx.versioned = True
    hits0 = tm.raw.policy.stats_version_gather_hits
    vals = rtx.read_bulk([target, base + 10])
    tm.commit(rtx)
    assert int(vals[0]) == 7 and int(vals[1]) == 7
    assert tm.raw.policy.stats_version_gather_hits == hits0
    tm.abort(wtx)
    tm.stop()
