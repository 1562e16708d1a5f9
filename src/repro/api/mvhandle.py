"""MVStoreHandle — the Layer-B MVStore behind the same Substrate protocol.

Wraps `mv_init/mv_commit/mv_snapshot` plus an `MVController` in the
begin/read/write/commit vocabulary of `repro.api`, so a snapshot read is
LITERALLY a read-only transaction — the same `@atomic` audit that runs on
the word-level Multiverse STM runs unchanged here:

  * the heap is ONE parameter block (an int32 vector); `alloc` grows it,
    `Txn.read/write` index into it;
  * an update transaction buffers writes (TL2-style) and publishes them as
    one `mv_commit` under a single-writer lock — the optimizer-step
    analogue — validating that the global clock has not advanced past its
    begin snapshot;
  * a read-only transaction validates the clock on the unversioned path
    (the Mode-Q reader that aborts when the writer commits first) and
    resolves ring versions at its read clock on the versioned path;
  * aborts feed the SAME K1/K2/K3 heuristics as the word level, via
    `MVController.ReaderHandle`: after K1 aborts a reader goes versioned
    (requesting ring versioning of the block), K2/K3 CAS the global mode
    Q -> QtoU, and the controller's background thread cycles the modes.

Values are numeric (this layer models parameter blocks); word substrates
additionally store arbitrary Python objects.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Tuple

import numpy as np

from repro.api.substrate import SubstrateBase, Txn
from repro.core import modes as M
from repro.core.engine import AbortTx
from repro.core.stats_schema import RECOVERY_STAT_KEYS, base_stats
from repro.reliability import faultpoints as FP
from repro.runtime.tracing import span

__all__ = ["ABORT_REASONS", "MVStoreHandle"]

_COUNTER_KEYS = ("commits", "aborts", "ro_commits", "versioned_commits")

#: why a transaction aborted, as ``stats()["abort_reasons"]`` counts it:
#:   clock            an unversioned read found the clock past its begin
#:   ring_window      a versioned read found no ring slot at/below its clock
#:   donation_race    a commit donated the row buffer the read was using
#:   commit_conflict  commit-time validation found a newer commit
#:   versioned_write  a versioned transaction wrote (restarts unversioned)
#:   explicit         ``abort`` of a live transaction (its body raised)
ABORT_REASONS = ("clock", "ring_window", "donation_race", "commit_conflict",
                 "versioned_write", "explicit")

_RACED = object()   # a device read lost the race against a donating commit


def _donation_raced(e: BaseException) -> bool:
    """True when a device read hit a buffer that ``mv_commit_fused``
    donated out from under it (jax spells it RuntimeError "Array has
    been deleted", XLA ValueError "buffer has been deleted or
    donated")."""
    msg = str(e)
    return "deleted" in msg or "donated" in msg


def _ring_slot(ring_ts, read_clock: int) -> Optional[int]:
    """Newest ring slot with a timestamp at/below ``read_clock``, or
    ``None`` when the clock fell out of the ring window (the one place
    the slot-selection idiom lives: scalar read, bulk read,
    ``snapshot_bulk`` and ``validate`` all route here)."""
    if ring_ts is None:
        return None
    valid = (ring_ts != -1) & (ring_ts <= read_clock)
    if not valid.any():
        return None
    return int(np.argmax(np.where(valid, ring_ts, -1)))


class _MVCtx:
    """Per-transaction context at the store level."""

    __slots__ = ("tid", "read_clock", "write_buf", "read_only", "read_cnt",
                 "active", "versioned")

    def __init__(self, tid: int):
        self.tid = tid
        self.read_clock = 0
        self.write_buf: dict = {}
        self.read_only = True
        self.read_cnt = 0
        self.active = False
        self.versioned = False


class MVStoreHandle(SubstrateBase):
    name = "mvstore"

    def __init__(self, n_threads: int = 1, *, cfg=None, params=None,
                 controller=None, versioned: str = "none",
                 start_bg: bool = True):
        import jax.numpy as jnp
        from repro.configs.base import MVStoreConfig
        from repro.configs.paper_stm import MultiverseParams
        from repro.core import mvstore
        from repro.core.mvcontroller import MVController

        self._jnp = jnp
        self._mvstore = mvstore
        self.n_threads = n_threads
        self.cfg = cfg or MVStoreConfig(ring_slots=8)
        self.params = params or MultiverseParams()
        self.controller = controller or MVController(
            params=self.params, mvcfg=self.cfg, start_bg=start_bg)
        self._own_controller = controller is None
        self._key = "heap"
        live = {self._key: jnp.zeros((0,), jnp.int32)}
        self._path = mvstore.block_paths(live)[0]
        self._commit_lock = threading.Lock()
        # crash-recovery slot (reliability/recovery.recover_handle): the
        # fused commit DONATES the old live/ring buffers, so between the
        # fused call and _install the ONLY reachable copy of the store is
        # this in-flight state — a crash there strands readers on deleted
        # buffers until recovery completes the install
        self._inflight = None
        # durable commit log (reliability/wal.py, via attach_wal): when
        # set, _publish_locked appends PREPARE + fsync'd DECIDE before
        # the donating fused call — the only window whole-process
        # recovery cannot rebuild from in-memory state
        self.wal = None
        self.wal_shard = -1
        self.recovery_counters = {k: 0 for k in RECOVERY_STAT_KEYS}
        self._readers = [self.controller.reader() for _ in range(n_threads)]
        self._counters = [{k: 0 for k in _COUNTER_KEYS}
                         for _ in range(n_threads)]
        self._abort_reasons = [dict.fromkeys(ABORT_REASONS, 0)
                               for _ in range(n_threads)]
        self._no_version = [False] * n_threads
        self._state = None
        self._snap: Tuple = (0, np.zeros((0,), np.int32), None, None)
        self._install(mvstore.mv_init(live, self.cfg, versioned=versioned))

    # -- state installation ----------------------------------------------
    def _install(self, state) -> None:
        """Publish a new MVStoreState plus the reader-visible snapshot.

        Readers only ever dereference `self._snap` — one immutable tuple
        replaced wholesale, so a read never sees half of a commit (the JAX
        buffer-immutability analogue of the paper's EBR argument).  The
        live block and ring stay DEVICE-RESIDENT jax buffers (scalar
        reads ``.item()`` one element; bulk reads gather on device via
        ``gather_row``'s jax-row branch) — only the tiny ``ring_ts``
        vector is materialized host-side, where ``_ring_slot``'s numpy
        scan wants it.  No per-commit host copy of the heap survives:
        the commit path hands the previous live/ring buffers to
        ``mv_commit_fused``, which DONATES them.  A reader pinned on
        the old ``_snap`` can therefore find its row deleted mid-read;
        that crash carries exactly the information a seqlock retry
        does — a commit raced us — so ``_read_device`` turns it into
        the abort the clock check would have issued an instant later
        (or a re-snapshot outside a transaction)."""
        ring = state.ring.get(self._path)
        if ring is not None:
            snap = (int(state.clock), state.live[self._key], ring,
                    np.asarray(state.ring_ts[self._path]))
        else:
            snap = (int(state.clock), state.live[self._key], None, None)
        self._state = state
        self._snap = snap

    def _read_device(self, fn, ctx: Optional[_MVCtx] = None):
        """One device read against snapshotted row buffers.

        ``mv_commit_fused`` donates the live/ring buffers it replaces,
        so a reader holding a pre-commit ``self._snap`` can lose its
        row mid-gather.  Inside a transaction that race IS the conflict
        the clock validation exists to catch — abort; outside one,
        return ``_RACED`` so the caller re-snapshots and retries."""
        try:
            return fn()
        except (RuntimeError, ValueError) as e:
            if not _donation_raced(e):
                raise
            if ctx is not None:
                self._abort_ctx(ctx, "donation_race")
            return _RACED

    # -- Substrate protocol ----------------------------------------------
    def begin_operation(self, tid: int) -> None:
        # no_versioning is per OPERATION: a versioned txn that writes must
        # restart unversioned, and must not be re-promoted on the next
        # abort of the same operation (the word-level livelock guard)
        self._no_version[tid] = False

    def begin(self, tid: int = 0) -> Txn:
        h = self._readers[tid]
        if self._no_version[tid]:
            h.versioned = False
        snap = self._snap
        ctx = _MVCtx(tid)
        ctx.read_clock = snap[0]
        h.begin(ctx.read_clock)
        ctx.versioned = h.versioned
        ctx.active = True
        return Txn(self, ctx, tid)

    def read(self, ctx: _MVCtx, addr: int) -> Any:
        ctx.read_cnt += 1
        if addr in ctx.write_buf:
            return ctx.write_buf[addr]
        versioned = ctx.versioned and ctx.read_only
        with span("mvstore.read", path="ring" if versioned else "live"):
            clock, live, ring, ring_ts = self._snap
            if versioned:
                if ring is None:
                    # Mode-Q reader versions the block itself (paper
                    # SS4.1's reader-triggered versioning, at block
                    # granularity)
                    clock, live, ring, ring_ts = self._version_block()
                slot = _ring_slot(ring_ts, ctx.read_clock)
                if slot is None:
                    self._abort_ctx(ctx, "ring_window")
                return self._read_device(lambda: ring[slot, addr].item(),
                                         ctx)
            # unversioned (Mode-Q reader / writer encounter read):
            # validate that no commit has advanced the clock past our
            # begin snapshot
            if clock > ctx.read_clock:
                self._abort_ctx(ctx, "clock")
            return self._read_device(lambda: live[addr].item(), ctx)

    def read_bulk(self, ctx: _MVCtx, addrs) -> Any:
        """`Txn.read_bulk` at the store level: one slice per batch.

        The store is already array-shaped, so the batch is literally one
        gather — of the live block on the unversioned path (after the
        same clock check every scalar read makes), or of the ONE ring row
        the reader's clock selects on the versioned path (slot selection
        is a host-side scan of the tiny timestamp vector; the row gather
        runs through ``kernels/gather_read.py`` on TPU).  A scanner that
        reads the whole block thus costs one launch, not N interpreter
        round-trips — the measurement the eval subsystem is built on.
        This is also the store-level substrate of the traversal layer:
        ``Txn.traverse_bulk``/``chase_bulk`` issue only ``read_bulk``
        calls, so struct walks over an MVStore block batch per frontier
        step exactly like the word-level engine.
        """
        from repro.core.engine.bulkread import as_addr_array
        a = as_addr_array(addrs)
        ctx.read_cnt += a.size
        versioned = ctx.versioned and ctx.read_only
        with span("mvstore.read_bulk", words=int(a.size),
                  path="ring" if versioned else "live"):
            clock, live, ring, ring_ts = self._snap
            if versioned:
                if ring is None:
                    clock, live, ring, ring_ts = self._version_block()
                slot = _ring_slot(ring_ts, ctx.read_clock)
                if slot is None:
                    self._abort_ctx(ctx, "ring_window")
                vals = self._read_device(
                    lambda: self._gather_row(ring[slot], a), ctx)
            else:
                if clock > ctx.read_clock:
                    self._abort_ctx(ctx, "clock")
                vals = self._read_device(
                    lambda: self._gather_row(live, a), ctx)
        if ctx.write_buf:
            return [ctx.write_buf.get(int(x), v)
                    for x, v in zip(a, vals.tolist())]
        return vals

    def _gather_row(self, row: np.ndarray, a: np.ndarray) -> np.ndarray:
        """One row gather (kernel-dispatched): the engine's shared
        ``gather_row`` serves the store's live block and ring rows too."""
        from repro.core.engine.bulkread import gather_row
        return gather_row(row, a)

    def write(self, ctx: _MVCtx, addr: int, value: Any) -> None:
        if ctx.versioned:
            # versioned reads are of the PAST and cannot anchor writes to
            # the present: restart on the unversioned path, sticky for
            # this operation (mirrors Multiverse.tm_write)
            self._no_version[ctx.tid] = True
            self._abort_ctx(ctx, "versioned_write")
        ctx.read_only = False
        ctx.write_buf[addr] = value

    def write_bulk(self, ctx: _MVCtx, addrs, values) -> None:
        """`Txn.write_bulk` at the store level: writes buffer until the
        single `mv_commit`, so the batch is one dict update — the commit
        itself already publishes the whole buffer through the shared
        scatter (``engine/commit.scatter_row``)."""
        if ctx.versioned:
            self._no_version[ctx.tid] = True
            self._abort_ctx(ctx, "versioned_write")
        ctx.read_only = False
        ctx.write_buf.update(zip((int(a) for a in addrs), values))

    def txn_alloc(self, ctx: _MVCtx, n: int, init: Any = None) -> int:
        # applied immediately, NOT rolled back on abort: block shapes are
        # step-boundary state at this layer, and an orphaned tail of the
        # heap block is harmless (unreachable until a committed write
        # publishes its address)
        return self.alloc(n, init)

    def _version_block(self) -> Tuple:
        """Seed a ring for the heap block with the live value.  Timestamp
        is the earliest safe one — firstObsModeUTs when valid, else the
        current clock (paper SS4.2); a reader whose snapshot is older than
        the seed then aborts via the no-valid-slot check."""
        with span("mvstore.version_block"), self._locked():
            state = self._state
            if self._path not in state.ring:
                state = self._mvstore.version_blocks(
                    state, {self._path}, self.cfg,
                    first_obs_mode_u_ts=self.controller.first_obs_mode_u_ts)
                self._install(state)
        return self._snap

    @contextlib.contextmanager
    def _locked(self):
        """Hold ``self._commit_lock``; the wait for it is the span
        ``mvstore.commit.lock_wait``."""
        with span("mvstore.commit.lock_wait"):
            self._commit_lock.acquire()
        try:
            yield
        finally:
            self._commit_lock.release()

    def commit(self, txn: Txn) -> None:
        ctx = txn._ctx
        h = self._readers[ctx.tid]
        c = self._counters[ctx.tid]
        if ctx.read_only:
            c["ro_commits"] += 1
            if ctx.versioned:
                c["versioned_commits"] += 1
            h.on_commit(ctx.read_cnt, commit_clock=self._snap[0])
            ctx.active = False
            return
        conflict = False
        with self._locked():
            if self._check_conflict(ctx):
                conflict = True            # another step committed first
            else:
                self._publish_locked(ctx)
        if conflict:
            self._abort_ctx(ctx, "commit_conflict")
        c["commits"] += 1
        h.attempts = 0
        ctx.active = False

    def _check_conflict(self, ctx: _MVCtx) -> bool:
        """Commit-time validation, ``self._commit_lock`` held: has any
        block this transaction touched been committed past its begin
        pin?  Per-block last-writer stamps (``mvstore.blocks_conflict``)
        — for the single-block handle this equals the old global
        ``clock != read_clock`` check (every commit stamps the one heap
        block); the sharded store calls it per shard so disjoint-shard
        commits never conflict."""
        return self._mvstore.blocks_conflict(
            self._state, (self._path,), ctx.read_clock)

    def _publish_locked(self, ctx: _MVCtx, wal_log: bool = True) -> None:
        """The publish half of commit, ``self._commit_lock`` held and
        validation already passed.  Also the recovery redo entry point:
        the cross-shard epoch roll-forward and the WAL replay drive a
        crashed member's parked context through exactly this path with
        ``wal_log=False`` (replay must not re-journal itself; the
        cross-shard caller journals the EPOCH instead)."""
        with span("mvstore.publish", words=len(ctx.write_buf)):
            if FP.ACTIVE is not None:
                FP.fire("pre_clock_tick", ctx.tid)
            state = self.controller.trainer_tick(self._state)
            mode = self.controller.current_local_mode()
            idx = np.array(sorted(ctx.write_buf), dtype=np.int64)
            vals = np.array([ctx.write_buf[int(i)] for i in idx])
            lsn = None
            if wal_log and self.wal is not None and idx.size:
                # PREPARE + DECIDE before the donating fused call: past the
                # donation the old buffers are GONE, so the WAL record is
                # the only thing a whole-process crash can recover from
                lsn = self.wal.append_prepare(
                    ctx.tid, idx, vals,
                    clocks=(int(self._state.clock) + 1,),
                    shard=self.wal_shard)
                self.wal.append_decide(lsn)
            # ONE fused publish under the held commit lock (the
            # seqlock bracket): scatter into the live row AND the
            # PackedVLT ring refresh ride a single device-resident
            # ``ops.commit_fused`` call — no scatter-then-rotate
            # host round trip (``mvstore.mv_commit_fused``).  The
            # fused call fires pre_scatter itself (before donation);
            # from the call's return until _install the new state is
            # parked in _inflight so recovery can finish the publish
            state = self._mvstore.mv_commit_fused(
                state, self._key, idx, vals, local_mode=mode,
                cfg=self.cfg)
            self._inflight = state
            if FP.ACTIVE is not None:
                FP.fire("post_scatter", ctx.tid)
                FP.fire("pre_release", ctx.tid)
            # the ring_ts host copy in _install waits for the fused call
            with span("mvstore.publish.install"):
                self._install(state)
            self._inflight = None
            if lsn is not None:
                self.wal.append_complete(lsn)

    def abort(self, txn: Txn) -> None:
        ctx = txn._ctx
        if not getattr(ctx, "active", False):
            return
        try:
            self._abort_ctx(ctx, "explicit")
        except AbortTx:
            pass

    def validate(self, ctx: _MVCtx) -> bool:
        """`Txn.validate_bulk` at the store level (read-only check).

        Unversioned transactions are valid while no commit has advanced
        the clock past their begin snapshot; versioned readers while the
        ring still holds a slot at/below their read clock.  One clock
        compare / one vectorized timestamp scan — the block-granularity
        analogue of the word engine's bulk read-set validation.
        """
        clock, live, ring, ring_ts = self._snap
        if ctx.versioned and ctx.read_only:
            if ring_ts is None:
                return True               # block not versioned yet
            return _ring_slot(ring_ts, ctx.read_clock) is not None
        return clock <= ctx.read_clock

    def _abort_ctx(self, ctx: _MVCtx, reason: str) -> None:
        """Count the abort under ``reason`` (one of ``ABORT_REASONS``),
        feed the heuristics, and raise ``AbortTx(reason)``."""
        self._counters[ctx.tid]["aborts"] += 1
        self._abort_reasons[ctx.tid][reason] += 1
        h = self._readers[ctx.tid]
        if ctx.read_only:
            # read-only aborts drive the paper's heuristics (K1 go-
            # versioned, K2/K3 mode CAS, block-versioning requests)
            h.on_abort(ctx.read_cnt, wanted_blocks=(self._path,))
        else:
            h.attempts += 1
        ctx.active = False
        raise AbortTx(reason)

    # -- heap -------------------------------------------------------------
    def alloc(self, n: int, init: Any = None) -> int:
        jnp = self._jnp
        fill = 0 if init is None else init
        with self._locked():
            state = self._state
            live = state.live[self._key]
            base = int(live.shape[0])
            was_versioned = self._path in state.ring
            new_live = {self._key: jnp.concatenate(
                [live, jnp.full((n,), fill, live.dtype)])}
            state = self._mvstore.MVStoreState(
                live=new_live, ring={}, ring_ts={}, clock=state.clock,
                block_clocks=state.block_clocks)
            if was_versioned:   # reseed the ring at the new block shape
                state = self._mvstore.version_blocks(
                    state, {self._path}, self.cfg,
                    first_obs_mode_u_ts=self.controller.first_obs_mode_u_ts)
            self._install(state)
        return base

    def peek(self, addr: int) -> Any:
        while True:
            v = self._read_device(lambda: self._snap[1][addr].item())
            if v is not _RACED:
                return v

    # -- Layer-B extras ----------------------------------------------------
    def snapshot(self, read_clock: Optional[int] = None):
        """(params_view, ok) via mv_snapshot — the functional spelling of a
        read-only transaction at `read_clock` (default: now)."""
        state = self._state
        if read_clock is None:
            read_clock = int(state.clock)
        return self._mvstore.mv_snapshot(state, read_clock)

    def snapshot_bulk(self, addrs, read_clock: Optional[int] = None):
        """``(values, ok)``: batched snapshot read outside any transaction.

        The functional spelling of `read_bulk` in a read-only transaction
        at ``read_clock`` (default: now): the current clock serves from
        the live block; a stale clock resolves through the ring (``ok``
        False when the block is unversioned or the clock fell out of the
        ring window — the cases a transactional reader would abort on).
        """
        from repro.core.engine.bulkread import as_addr_array
        a = as_addr_array(addrs)
        while True:
            clock, live, ring, ring_ts = self._snap
            if read_clock is None or read_clock >= clock:
                vals = self._read_device(lambda: self._gather_row(live, a))
            else:
                slot = _ring_slot(ring_ts, read_clock)
                if slot is None:
                    return None, False
                vals = self._read_device(
                    lambda: self._gather_row(ring[slot], a))
            if vals is not _RACED:
                return vals, True

    @property
    def state(self):
        """The underlying MVStoreState (trainer integration)."""
        return self._state

    @property
    def clock(self) -> int:
        return self._snap[0]

    # -- stats / lifecycle -------------------------------------------------
    def stats(self) -> dict:
        out = base_stats(backend=self.name,
                         mode=M.mode_name(self.controller.mode_counter))
        for c in self._counters:
            for k in _COUNTER_KEYS:
                out[k] += c[k]
        out["abort_reasons"] = {
            r: sum(c[r] for c in self._abort_reasons) for r in ABORT_REASONS}
        out["mode_cas"] = sum(h.stats["mode_cas"] for h in self._readers)
        out["mode_transitions"] = self.controller.stats["mode_transitions"]
        out["unversioned_buckets"] = self.controller.stats[
            "blocks_unversioned"]
        for k, v in self.recovery_counters.items():
            out[k] += v
        return out

    def stop(self) -> None:
        if self._own_controller:
            self.controller.stop()
