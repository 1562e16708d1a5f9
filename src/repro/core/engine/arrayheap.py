"""Array-backed heap + lock table: the engine's vectorizable substrate.

Two heap flavors behind one three-method interface (``alloc`` /
``__getitem__`` / ``__setitem__``):

  * ``ObjectHeap`` — the historical Python list; holds arbitrary objects
    (struct tests store strings), the default for every backend;
  * ``ArrayHeap``  — words in a contiguous int64 numpy buffer with
    capacity doubling and an on-demand ``jnp()`` view, so bulk kernels
    (``kernels/validate.py``, future sharded stores) can touch the whole
    heap in one launch.  Numeric words only.

``ArrayLockTable`` packs each versioned lock word ``(locked, version,
tid, flag)`` into ONE int64 array element::

    bits 18..63  version        (commit clock)
    bits  2..17  tid + 2        (supports the -2 background/-1 none tids)
    bit   1      locked
    bit   0      flag           (versioning-in-progress)

A single packed word makes the bulk path sound: ``gather(idxs)`` fancy-
indexes the array ONCE, so each gathered element is a consistent
(locked, version, tid, flag) tuple — gathering parallel arrays field by
field could tear a word between fields, which the scalar path never does.
"""
from __future__ import annotations

import threading
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.core.locks import LockState, LockTable

_TID_BIAS = 2                    # stored tid = tid + 2 (tid >= -2)
_TID_BITS = 16
_TID_MASK = (1 << _TID_BITS) - 1
_VER_SHIFT = 2 + _TID_BITS


def pack_lock(st: LockState) -> int:
    return ((st.version << _VER_SHIFT)
            | ((st.tid + _TID_BIAS) & _TID_MASK) << 2
            | (1 << 1 if st.locked else 0)
            | (1 if st.flag else 0))


def unpack_lock(word: int) -> LockState:
    return LockState(bool(word & 2), word >> _VER_SHIFT,
                     ((word >> 2) & _TID_MASK) - _TID_BIAS, bool(word & 1))


def unpack_words(words) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Packed lock words -> ``(version int64, owner int32, meta int32)``
    with meta bit0 = locked, bit1 = flag — the layout the bulk
    validators (numpy and the Pallas kernels) consume."""
    w = np.asarray(words, np.int64)
    ver = w >> _VER_SHIFT
    own = (((w >> 2) & _TID_MASK) - _TID_BIAS).astype(np.int32)
    meta = (((w >> 1) & 1) | ((w & 1) << 1)).astype(np.int32)
    return ver, own, meta


_UNLOCKED_WORD = pack_lock(LockState(False, 0, -1, False))


def _fits32(v: int) -> bool:
    """The int32 range the device path admits (``ops.beyond_int32``)."""
    return -(1 << 31) < v < (1 << 31)


def check_addr_bounds(idx: np.ndarray, n: int) -> None:
    """Raise unless every address lands in ``[0, n)`` — the bounds
    contract every bulk gather/scatter shares, failing loudly at BOTH
    ends: past the frontier (matching the scalar accessors) AND
    negative, which would wrap under numpy/jax fancy indexing and
    silently hit a word near the end of the buffer."""
    if not idx.size:
        return
    lo, hi = int(idx.min()), int(idx.max())
    if lo < 0 or hi >= n:
        raise IndexError(lo if lo < 0 else hi)


class ObjectHeap:
    """Plain Python-list heap: any value, no vectorization."""

    def __init__(self):
        self._cells: List[Any] = []
        self._lock = threading.Lock()

    def alloc(self, n: int, init: Any = None) -> int:
        with self._lock:
            base = len(self._cells)
            self._cells.extend([init] * n)
            return base

    def __getitem__(self, addr: int) -> Any:
        return self._cells[addr]

    def __setitem__(self, addr: int, value: Any) -> None:
        self._cells[addr] = value

    def __len__(self) -> int:
        return len(self._cells)

    def gather(self, addrs) -> List[Any]:
        """Batched read (``Txn.read_bulk``): one pass, no vectorization
        possible over arbitrary objects — but still one bounds check and
        no per-word lock/validate Python round-trips."""
        cells = self._cells
        return [cells[int(a)] for a in addrs]

    def scatter(self, addrs, values) -> None:
        """Batched write-back (the commit pipeline's ``write_back``):
        one pass over arbitrary objects — the list analogue of
        ``ArrayHeap.scatter``, so the bulk commit path has one
        interface on both heaps."""
        cells = self._cells
        for a, v in zip(addrs, values):
            cells[int(a)] = v


class ArrayHeap:
    """Numeric word heap in one int64 numpy buffer (doubling growth).

    ``len()`` is the allocated frontier, not the capacity; reads beyond it
    raise like the list heap does.  ``jnp()`` returns the live words as a
    jax array (a copy — jax buffers are immutable) for kernel consumption.
    """

    def __init__(self, capacity: int = 1024):
        self._buf = np.zeros(max(capacity, 1), np.int64)
        self._len = 0
        self._lock = threading.Lock()
        #: sticky: some stored word left the int32 range (see fits_int32)
        self._wide = False

    def alloc(self, n: int, init: Any = None) -> int:
        fill = 0 if init is None else int(init)
        if not _fits32(fill):
            self._wide = True
        with self._lock:
            base = self._len
            need = base + n
            if need > self._buf.shape[0]:
                cap = self._buf.shape[0]
                while cap < need:
                    cap *= 2
                grown = np.zeros(cap, np.int64)
                grown[:base] = self._buf[:base]
                self._buf = grown
            self._buf[base:need] = fill
            self._len = need
            return base

    def __getitem__(self, addr: int) -> int:
        # both ends: a negative address would wrap to the end of the
        # buffer (numpy indexing), same contract as the bulk paths
        if addr < 0 or addr >= self._len:
            raise IndexError(addr)
        return int(self._buf[addr])

    def __setitem__(self, addr: int, value: Any) -> None:
        if addr < 0 or addr >= self._len:
            raise IndexError(addr)
        # under the lock: a concurrent alloc() may be copying into a grown
        # buffer, and a write that raced the copy would land in the
        # discarded old array and silently vanish (ObjectHeap never
        # rebinds its list, so only the array heap has this hazard)
        value = int(value)
        if not _fits32(value):
            self._wide = True
        with self._lock:
            self._buf[addr] = value

    def __len__(self) -> int:
        return self._len

    def gather(self, addrs) -> np.ndarray:
        """Batched read: one fancy-index copy of ``buf[addrs]``.

        The copy is taken under the heap lock so a concurrent ``alloc``
        cannot swap the buffer out mid-gather (the same hazard
        ``__setitem__`` guards against); each element is then a plain
        int64 word.  Bounds are checked against the allocation frontier,
        matching the scalar ``__getitem__`` contract.
        """
        idx = np.asarray(addrs, np.int64)
        with self._lock:
            check_addr_bounds(idx, self._len)
            return self._buf[idx]

    def scatter(self, addrs, values) -> None:
        """Batched write-back: one fancy-index assignment of
        ``buf[addrs] = values`` under the heap lock (the same
        buffer-swap hazard ``__setitem__`` guards against).  Bounds are
        checked against the allocation frontier, matching the scalar
        ``__setitem__`` contract; values coerce through int64 exactly
        like the scalar ``int(value)`` does.  Addresses must be unique
        (write sets are dict-keyed) — with duplicates numpy keeps an
        unspecified writer, where the scalar loop keeps the last.
        """
        idx = np.asarray(addrs, np.int64)
        vals = np.asarray(values)
        if vals.dtype.kind not in "iu":       # match scalar int(value)
            vals = np.fromiter((int(v) for v in values), np.int64,
                               idx.size)
        if vals.size and not (_fits32(int(vals.min()))
                              and _fits32(int(vals.max()))):
            self._wide = True
        with self._lock:
            check_addr_bounds(idx, self._len)
            self._buf[idx] = vals

    @property
    def fits_int32(self) -> bool:
        """False once a word beyond the int32 range was stored.  The
        device copy is int32 (the repo never enables jax x64), so such a
        heap must take the numpy twins instead of the kernels.  Sticky:
        overwriting the word later does not clear it."""
        return not self._wide

    def jnp(self):
        """The live words as a jax int32 array, for the device kernels.
        Raises OverflowError when ``fits_int32`` is False rather than
        truncating a word."""
        import jax.numpy as jnp
        if self._wide:
            raise OverflowError(
                "heap holds a word beyond int32; its device copy would "
                "truncate it")
        return jnp.asarray(self._buf[:self._len])


class ArrayLockTable(LockTable):
    """``LockTable`` semantics over a packed int64 numpy array.

    Inherits ``validate``/``try_lock``/``index`` (they are written against
    ``read``/``cas``) and overrides only the storage layer, adding the two
    bulk operations the vectorized hot path needs: ``gather`` and
    ``held_by``.
    """

    def __init__(self, bits: int):
        self.bits = bits
        self.size = 1 << bits
        self._words = np.full(self.size, _UNLOCKED_WORD, np.int64)
        from repro.core.clock import Striped
        # 128 stripes, not 1024: a bulk sweep acquires every DISTINCT
        # stripe its batch covers, so stripe count bounds the per-sweep
        # Python lock traffic (a 1k-word claim is <=128 acquires, not
        # ~1k) — while scalar CAS contention, which stripes exist to
        # spread, stays negligible at this port's thread counts
        self._stripes = Striped(128)

    # -- storage ops -------------------------------------------------------
    def read(self, idx: int) -> LockState:
        return unpack_lock(int(self._words[idx]))

    def read_wait_unflagged(self, idx: int) -> LockState:
        while True:
            w = int(self._words[idx])
            if not (w & 1):
                return unpack_lock(w)

    def cas(self, idx: int, expect: LockState, new: LockState) -> bool:
        with self._stripes.for_index(idx):
            if int(self._words[idx]) != pack_lock(expect):
                return False
            self._words[idx] = pack_lock(new)
            return True

    def store(self, idx: int, new: LockState) -> None:
        with self._stripes.for_index(idx):
            self._words[idx] = pack_lock(new)

    def lock_and_flag(self, idx: int, tid: int) -> LockState:
        while True:
            st = unpack_lock(int(self._words[idx]))
            if not st.locked and not st.flag:
                if self.cas(idx, st, LockState(True, st.version, tid, True)):
                    return st

    def unlock(self, idx: int, version: Optional[int] = None) -> None:
        with self._stripes.for_index(idx):
            st = unpack_lock(int(self._words[idx]))
            v = version if version is not None else st.version
            self._words[idx] = pack_lock(LockState(False, v, -1, False))

    # -- bulk ops ----------------------------------------------------------
    def index_bulk(self, addrs: np.ndarray) -> np.ndarray:
        """Vectorized ``index``: the Fibonacci hash of many addresses at
        once (uint64 arithmetic wraps mod 2**64 exactly like the scalar
        Python path masks it)."""
        from repro.core.locks import _GOLDEN
        a = np.asarray(addrs, np.uint64) * np.uint64(_GOLDEN)
        return (a >> np.uint64(64 - self.bits)).astype(np.int64)

    def gather(self, idxs: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
        """One consistent snapshot of many lock words.

        Returns ``unpack_words`` of the words at ``idxs``.
        """
        return unpack_words(self._words[idxs])      # single fancy-index copy

    def held_by(self, tid: int) -> np.ndarray:
        """Indices currently write-locked by ``tid`` (exhaustion cleanup)."""
        w = self._words
        mask = ((w & 2) != 0) & ((((w >> 2) & _TID_MASK) - _TID_BIAS) == tid)
        return np.nonzero(mask)[0]

    def try_lock_bulk(self, idxs: np.ndarray, tid: int,
                      max_version: Optional[int] = None
                      ) -> Optional[np.ndarray]:
        """All-or-nothing bulk claim: one CAS sweep over many indices.

        Deduplicates ``idxs`` (colliding addresses share a lock word,
        exactly like the scalar acquire loop's ``if idx not in locked``),
        then — holding every covering stripe, acquired in ascending
        order — checks the whole batch with ONE gather and, only if
        every word is claimable, claims the free ones with ONE scatter.
        Claimable means: free and unflagged (a word locked or flagged by
        someone else conflicts; a word locked by ``tid`` passes
        untouched), and — when ``max_version`` is given — free words
        must also carry ``version < max_version`` (the encounter-time
        write's validate-then-lock, atomically: the version is checked
        under the same stripes the claim holds, so it cannot advance in
        between like a separate gather would allow).

        On ANY conflict nothing is mutated and ``None`` returns (the
        scalar loop releases what it had acquired; the bulk sweep never
        acquires in the first place — same end state, no partial-hold
        window for other writers to conflict on).

        Returns the NEWLY-ACQUIRED unique indices (ascending int64[n]) —
        words already held by ``tid`` are excluded, so an unwinding
        caller can release exactly what this call took without touching
        locks earlier writes legitimately hold.  Per-word claim
        semantics match ``try_lock``: version preserved, flag cleared.
        """
        uniq = np.unique(np.asarray(idxs, np.int64))

        def conflicts(w):
            locked = (w & 2) != 0
            flagged = (w & 1) != 0
            own = locked & ((((w >> 2) & _TID_MASK) - _TID_BIAS) == tid)
            c = (locked | flagged) & ~own
            if max_version is not None:
                c |= ~locked & ((w >> _VER_SHIFT) >= max_version)
            return c

        # test-and-test-and-set: a conflict visible in a plain gather is
        # authoritative for FAILING (the caller retries/aborts either
        # way), so the common doomed sweep skips the stripe dance
        if bool(conflicts(self._words[uniq]).any()):
            return None
        stripes = self._stripes.for_indices(uniq)
        for s in stripes:
            s.acquire()
        try:
            w = self._words[uniq]
            if bool(conflicts(w).any()):
                return None
            locked = (w & 2) != 0
            free = ~locked
            new = ((w >> _VER_SHIFT) << _VER_SHIFT) \
                | (((tid + _TID_BIAS) & _TID_MASK) << 2) | 2
            self._words[uniq[free]] = new[free]
            return uniq[free]
        finally:
            for s in stripes:
                s.release()

    def striped(self, idxs: np.ndarray):
        """Context manager holding every stripe covering ``idxs``
        (acquired ascending, like the bulk sweeps) — the group-commit
        batcher's atomicity bracket: gather + verdict + claim run as one
        hoisted CAS window instead of per-transaction sweeps.  Pair with
        ``words_at``/``store_words``; do NOT call the self-locking ops
        (``try_lock_bulk``/``unlock_bulk``/``cas``) inside."""
        from contextlib import contextmanager

        stripes = self._stripes.for_indices(np.asarray(idxs, np.int64))

        @contextmanager
        def _hold():
            for s in stripes:
                s.acquire()
            try:
                yield
            finally:
                for s in stripes:
                    s.release()

        return _hold()

    def words_at(self, idxs: np.ndarray) -> np.ndarray:
        """Raw packed words, one consistent fancy-index copy — the group
        commit's gather (fields come from the shared bit math in
        ``kernels/commit_fused``'s caller)."""
        return self._words[np.asarray(idxs, np.int64)]

    def store_words(self, idxs: np.ndarray, words: np.ndarray) -> None:
        """Raw word scatter.  Caller MUST hold ``striped(idxs)`` (or the
        words must be claim words only this thread may release) — this
        is the storage primitive under the batcher's claim/stamp steps,
        with no locking of its own."""
        self._words[np.asarray(idxs, np.int64)] = words

    def claim_words(self, words: np.ndarray, tids: np.ndarray) -> np.ndarray:
        """Locked spellings of ``words`` claimed by per-entry ``tids``
        (version preserved, flag cleared) — vectorized ``try_lock``'s
        store half for the group claim."""
        return ((words >> _VER_SHIFT) << _VER_SHIFT) \
            | (((np.asarray(tids, np.int64) + _TID_BIAS) & _TID_MASK) << 2) \
            | 2

    def unlock_bulk(self, idxs: np.ndarray,
                    version: Optional[int] = None) -> None:
        """Release many locks in one sweep (commit publish / rollback).

        ``version`` republishes every word at that clock (the commit /
        deferred-clock-abort paths); ``None`` preserves each word's
        current version (the failed-acquire cleanup path).  Duplicate
        indices are safe WITHIN the sweep — every occurrence stores the
        same unlocked word while the stripes are held, so no explicit
        dedup pass is needed (unlike repeated scalar ``unlock`` calls,
        where a second release could stomp a lock another thread
        acquired in between — the hazard ``engine/commit.py``'s index
        normalization exists for).
        """
        arr = np.asarray(idxs, np.int64)
        stripes = self._stripes.for_indices(arr)
        for s in stripes:
            s.acquire()
        try:
            if version is None:
                w = self._words[arr]
                self._words[arr] = ((w >> _VER_SHIFT) << _VER_SHIFT) \
                    | _UNLOCKED_WORD
            else:
                self._words[arr] = (version << _VER_SHIFT) \
                    | _UNLOCKED_WORD
        finally:
            for s in stripes:
                s.release()
