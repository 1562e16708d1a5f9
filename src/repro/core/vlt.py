"""Version List Table (paper SS3.1, Fig. 2) + its packed bulk mirror.

Each bucket is a linked list of VLT nodes; a node holds (1) the head of a
version list, (2) the address it tracks, (3) the next bucket node.  Version
lists are linked lists of VListNode(older, timestamp, data, tbd), newest
first.  The address's lock (same index) protects all VLT mutations.

DELETED_TS marks versions rolled back by an aborted writer so concurrent
traversals are never permanently blocked on a TBD mark (paper SS4.1).

The bucket lists are what writers MUTATE; what bulk readers need is a
gather-friendly view of what they would FIND.  ``PackedVLT`` is that
view: an int64 mirror, indexed like the lock table, of each bucket's
newest ``depth`` COMMITTED ``(timestamp, data)`` pairs, maintained under
the same address lock that protects the list mutations and bracketed by
a per-row seqlock for lock-free readers.  A versioned bulk read
(``engine/bulkread.py`` Mode-U/Q hybrid path, paper SS4.2) resolves its
recently-written minority through ONE ``PackedVLT.select`` gather —
numpy twin ``np_version_select`` on CPU, the
``kernels/version_select.py`` Pallas kernel on TPU — instead of walking
version lists node by node in Python.  Rows the mirror cannot represent
(hash-colliding addresses sharing a bucket, non-integer payloads,
versions deeper than ``depth``) simply fail ``select`` and fall back to
the exact scalar traversal, so the mirror is an optimization of the
common case, never a semantic change.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

DELETED_TS = -2

#: empty mirror slot: never strictly below any snapshot clock, so the
#: selection predicate rejects it with no special-casing (rebased to the
#: int32-saturated positive sentinel on the kernel path)
EMPTY_TS = 1 << 62


class VListNode:
    __slots__ = ("older", "timestamp", "data", "tbd", "freed")

    def __init__(self, older, timestamp, data, tbd):
        self.older = older
        self.timestamp = timestamp
        self.data = data
        self.tbd = tbd
        self.freed = False          # EBR poison bit (use-after-free checks)


class VersionList:
    __slots__ = ("head",)

    def __init__(self, head: Optional[VListNode] = None):
        self.head = head


class VLTNode:
    __slots__ = ("vlist", "addr", "next", "freed")

    def __init__(self, vlist: VersionList, addr: int,
                 nxt: Optional["VLTNode"]):
        self.vlist = vlist
        self.addr = addr
        self.next = nxt
        self.freed = False


def np_version_select(ts: np.ndarray, data: np.ndarray,
                      r_clock: int) -> Tuple[np.ndarray, np.ndarray]:
    """Newest committed version strictly below ``r_clock``, per row.

    ``ts``/``data`` are [N, depth] newest-first mirror rows; returns
    ``(values [N], ok [N] bool)`` with ``values`` meaningful only where
    ``ok``.  Strict ``<`` mirrors the scalar traverse's acceptance (the
    deferred clock shares timestamps across commits; DESIGN.md SS6).
    The same contract is implemented by ``kernels/version_select.py`` —
    the kernel test pins the two element-for-element.
    """
    valid = ts < r_clock
    ok = valid.any(axis=1)
    first = np.argmax(valid, axis=1)
    vals = data[np.arange(ts.shape[0]), first]
    return vals, ok


def _packable(data) -> bool:
    """Only plain int64-range integers ride in the packed mirror."""
    return type(data) in (int, np.int64, np.int32) and \
        -(1 << 62) < int(data) < (1 << 62)


class PackedVLT:
    """Gather-friendly mirror of each bucket's newest committed versions.

    Arrays indexed by lock-table index: ``seq`` (per-row seqlock),
    ``addr`` ([size, ways] — WHICH addresses each row tracks, or a
    sentinel per way), and the newest-first ``ts``/``data`` version
    slots ([size, ways, depth]).  A bucket collision no longer poisons
    the row: the second address hashing into a bucket claims the second
    WAY and both stay vectorizable (``way_hits[w]`` counts reads each
    way served); only when every way is taken does a further colliding
    address go unmirrored — it simply never matches ``select`` and
    falls back to the scalar walk.  WRITERS mutate a row only while
    holding the row's address lock, bumping ``seq`` odd before and even
    after, so the scalar path's lock discipline also serializes mirror
    updates.  READERS hold nothing: ``select`` brackets its gathers
    with two ``seq`` gathers and accepts only rows that were stable and
    even across the window — a torn row just falls back to the scalar
    version-list walk.

    TBD (uncommitted) versions are never mirrored, so callers MUST gate
    acceptance on the address lock being free, gathered BEFORE the row
    (``MultiversePolicy._bulk_versioned_gather``): a commit whose clock
    was loaded before the reader began — and which can therefore still
    publish BELOW the reader's snapshot — holds its address locks for
    its entire publish window, and serving the mirror mid-window could
    mix pre- and post-commit values across a multi-address commit.
    With the gate, a writer locking after the gather commits at/above
    the snapshot and is skipped by strict ``ts < r_clock`` regardless —
    the same versions the scalar traverse waits on and then skips.
    """

    NO_ADDR = -1       # way empty (tracks no versioned address)
    UNPACKABLE = -2    # way poisoned (non-int payload reached a tracked
    #                    address): never matches select -> scalar fallback

    def __init__(self, size: int, depth: int = 4, ways: int = 2):
        self.size = size
        self.depth = depth
        self.ways = ways
        self._seq = np.zeros(size, np.int64)
        self._addr = np.full((size, ways), self.NO_ADDR, np.int64)
        self._ts = np.full((size, ways, depth), EMPTY_TS, np.int64)
        self._data = np.zeros((size, ways, depth), np.int64)
        #: reads served per way (way_hits[1:] are the collision wins the
        #: multi-way layout buys — exposed as stats_mirror_way2_hits)
        self.way_hits = [0] * ways

    def _way_of(self, bucket: int, addr: int) -> Optional[int]:
        w = np.nonzero(self._addr[bucket] == addr)[0]
        return int(w[0]) if w.size else None

    # -- writer side (caller holds the address lock for ``bucket``) ------
    def seed(self, bucket: int, addr: int, head: VListNode) -> None:
        """A version list was inserted for ``addr`` in ``bucket``: claim
        the first free way.  Unrepresentable heads (TBD, deleted,
        non-int payloads) and way overflow claim NOTHING — an unmirrored
        address never matches ``select``, which is already the safe
        fail-closed answer."""
        if head is None or head.tbd or head.timestamp == DELETED_TS \
                or not _packable(head.data):
            return
        free = np.nonzero(self._addr[bucket] == self.NO_ADDR)[0]
        if not free.size:
            return                     # all ways busy: not mirrored
        w = int(free[0])
        self._seq[bucket] += 1
        self._addr[bucket, w] = addr
        self._ts[bucket, w, 0] = head.timestamp
        self._ts[bucket, w, 1:] = EMPTY_TS
        self._data[bucket, w, 0] = int(head.data)
        self._seq[bucket] += 1

    def publish(self, bucket: int, addr: int, ts: int, data) -> None:
        """A commit published a NEW newest version for ``addr``."""
        w = self._way_of(bucket, addr)
        if w is None:
            return                     # unmirrored/poisoned: no-op
        self._seq[bucket] += 1
        if _packable(data):
            self._ts[bucket, w, 1:] = self._ts[bucket, w, :-1]
            self._data[bucket, w, 1:] = self._data[bucket, w, :-1]
            self._ts[bucket, w, 0] = ts
            self._data[bucket, w, 0] = int(data)
        else:
            # the newest version is unrepresentable; serving older slots
            # would time-travel, so the way must fall back until cleared
            self._addr[bucket, w] = self.UNPACKABLE
        self._seq[bucket] += 1

    def publish_bulk(self, buckets: np.ndarray, addrs: np.ndarray,
                     ts: int, datas) -> None:
        """One batched mirror refresh for a whole commit's version set
        (caller holds every address lock; ``MultiversePolicy``'s batched
        ``commit_update``).  Per UNIQUE bucket a single seqlock bracket
        — NOT one per entry: two ways of one bucket bumped separately
        would pass through an even mid-update ``seq`` and a reader could
        accept a half-refreshed row.  The slot shift itself is one
        vectorized assignment over all matched (bucket, way) pairs;
        unpackable payloads take the scalar ``publish`` (which poisons
        their way) after the sweep.
        """
        b = np.asarray(buckets, np.int64)
        a = np.asarray(addrs, np.int64)
        packable = np.fromiter((_packable(x) for x in datas), bool, a.size)
        vals = np.fromiter((int(x) if ok else 0
                            for x, ok in zip(datas, packable)),
                           np.int64, a.size)
        match = self._addr[b] == a[:, None]            # [M, ways]
        way = np.argmax(match, axis=1)
        tracked = match.any(axis=1)
        hit = tracked & packable
        if hit.any():
            hb, hw = b[hit], way[hit]                  # distinct pairs:
            # a way tracks ONE address and addrs are dict-keyed unique
            uniq = np.unique(hb)
            self._seq[uniq] += 1
            self._ts[hb, hw, 1:] = self._ts[hb, hw, :-1]
            self._data[hb, hw, 1:] = self._data[hb, hw, :-1]
            self._ts[hb, hw, 0] = ts
            self._data[hb, hw, 0] = vals[hit]
            self._seq[uniq] += 1
        for i in np.nonzero(tracked & ~packable)[0]:
            self.publish(int(b[i]), int(a[i]), ts, datas[int(i)])

    def clear(self, bucket: int) -> None:
        """The bucket was unversioned (paper SS4.4): forget everything."""
        self._seq[bucket] += 1
        self._addr[bucket] = self.NO_ADDR
        self._ts[bucket] = EMPTY_TS
        self._seq[bucket] += 1

    # -- reader side (lock-free) -----------------------------------------
    def select(self, idxs: np.ndarray, addrs: np.ndarray,
               r_clock: int) -> Tuple[np.ndarray, np.ndarray]:
        """Batched version resolution: ``(values int64[N], ok bool[N])``.

        ``values[i]`` is the newest committed version of ``addrs[i]``
        strictly below ``r_clock`` wherever ``ok[i]``; everywhere else
        the caller re-reads through the scalar traverse.  One seqlock-
        bracketed gather of the mirror rows, a vectorized way match,
        then one vectorized select over the matched ways (numpy twin on
        CPU, the Pallas kernel on TPU).
        """
        s1 = self._seq[idxs]
        rows_addr = self._addr[idxs]                   # [N, ways]
        ts = self._ts[idxs]                            # [N, ways, depth]
        data = self._data[idxs]
        s2 = self._seq[idxs]
        stable = (s1 == s2) & ((s1 & 1) == 0)
        match = rows_addr == addrs[:, None]
        way = np.argmax(match, axis=1)                 # first (only) match
        rows = np.arange(idxs.shape[0])
        ts_w, data_w = ts[rows, way], data[rows, way]  # [N, depth]
        from repro.kernels import ops
        if ops.on_tpu():
            vals, found = ops.version_select(ts_w, data_w, r_clock)
        else:
            vals, found = np_version_select(ts_w, data_w, r_clock)
        ok = stable & match.any(axis=1) & found
        for w in range(1, self.ways):
            n = int((ok & (way == w)).sum())
            if n:
                self.way_hits[w] += n
        return vals, ok


class VLT:
    def __init__(self, buckets_bits: int, mirror_depth: int = 4):
        self.size = 1 << buckets_bits
        self._buckets: List[Optional[VLTNode]] = [None] * self.size
        self.mirror = PackedVLT(self.size, depth=mirror_depth)
        #: live count of nonempty buckets, guarded by ``_count_lock``:
        #: ``+=`` on an attribute is a preemptible load/add/store, and
        #: two inserts under DIFFERENT bucket locks could lose an
        #: increment — after which the count could read 0 with a bucket
        #: still populated, and the batched Mode-Q write path would skip
        #: version publication (a silent snapshot violation).  Reads are
        #: single attribute loads and need no lock.  The gate itself is
        #: sound: 0 proves every lock-frozen bucket in a write batch is
        #: empty without walking them (an insert needs the bucket's
        #: address lock, so a batch's own buckets cannot gain version
        #: lists while the batch holds their locks).
        self.nonempty_count = 0
        self._count_lock = threading.Lock()

    def get(self, bucket: int, addr: int) -> Optional[VersionList]:
        """tryGetVList: walk the bucket list (caller saw a bloom hit)."""
        node = self._buckets[bucket]
        while node is not None:
            assert not node.freed, "use-after-free: VLT node"
            if node.addr == addr:
                return node.vlist
            node = node.next
        return None

    def insert(self, bucket: int, addr: int, vlist: VersionList) -> None:
        """Prepend (caller holds the address lock)."""
        if self._buckets[bucket] is None:
            with self._count_lock:
                self.nonempty_count += 1
        self._buckets[bucket] = VLTNode(vlist, addr, self._buckets[bucket])
        self.mirror.seed(bucket, addr, vlist.head)

    def take_bucket(self, bucket: int) -> Optional[VLTNode]:
        """Detach the whole bucket (unversioning; caller holds the lock)."""
        head = self._buckets[bucket]
        if head is not None:
            with self._count_lock:
                self.nonempty_count -= 1
        self._buckets[bucket] = None
        self.mirror.clear(bucket)
        return head

    def bucket_newest_ts(self, bucket: int) -> Optional[int]:
        """Most recent (non-TBD) timestamp in the bucket, for the
        unversioning heuristic (paper SS4.4)."""
        newest = None
        node = self._buckets[bucket]
        while node is not None:
            v = node.vlist.head
            while v is not None and (v.tbd or v.timestamp == DELETED_TS):
                v = v.older
            if v is not None and (newest is None or v.timestamp > newest):
                newest = v.timestamp
            node = node.next
        return newest

    def nonempty_buckets(self):
        return [i for i in range(self.size) if self._buckets[i] is not None]
