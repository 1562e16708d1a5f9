"""ShardStoreHandle — the MVStore partitioned across a jax mesh of shards.

The tentpole of the two-level clock scheme (``mvstore.MVStoreState.
block_clocks`` is the fine level; this module is the coarse level):
``n_shards`` independent ``MVStoreHandle``s, each owning one slice of
the address space, one shard-local clock and its own bounded rings —
plus ONE coarse epoch clock for cross-shard ordering.  Commits to
disjoint shards tick independently and never conflict; that is the
paper's footprints-only-conflict-when-they-overlap promise lifted from
blocks to devices.

Address routing: the global space is striped in spans of ``span``
words — global address ``a`` lives in span ``k = a // span``, which
shard ``k % n_shards`` stores at local address
``(k // n_shards) * span + a % span``.  At ``n_shards == 1`` the map is
the identity, so the sharded store is BIT-IDENTICAL to a solo
``MVStoreHandle`` on the same seeds (the conformance suite pins this).
When the host exposes multiple jax devices (or a mesh is passed), each
shard's buffers are ``device_put`` onto its own device slice via the
``launch/mesh.py`` + ``launch/sharding.py`` machinery — one shard = one
device slice; on a single-device host placement is a no-op and the
partitioning still buys clock independence.

Transaction lifecycle (the two-level clock protocol):

  * ``begin`` pins a VECTOR of shard clocks — one sub-context per
    shard — under an epoch seqlock bracket: the pin loop re-reads the
    epoch sequence (odd = a cross-shard publish is mid-flight) and
    retries until it pinned a stable, even cut.  Single-shard commits
    never bump the sequence, so the common case costs two atomic loads.
  * reads/writes route to the owning shard and validate against that
    shard's pin (``read_bulk`` batches per shard through
    ``engine/bulkread.shard_partition`` and reassembles in order).
  * commit with a SINGLE-shard footprint (reads and writes on one
    shard — the common case) delegates to that shard's solo commit: no
    coordination, no epoch traffic, exactly today's pipeline.
  * commit SPANNING shards runs a two-phase epoch-stamped publish:
    acquire every involved shard's commit lock in ascending shard order
    (``engine/commit.acquire_ascending`` — the ``Striped.for_indices``
    discipline lifted to whole commit locks), validate EVERY touched
    shard against its pin under the locks (atomic
    validate-all-then-publish-all: a read-shard/write-shard split can
    never produce a non-serializable cut), park an
    ``EpochRecord`` (``reliability/recovery.py`` — ``publish_started``
    generalized to the epoch), bump the epoch seqlock odd, publish
    shard-locally through each shard's exact solo publish path, then
    even the seqlock.  A crash mid-epoch leaves the record parked and
    the sequence odd; ``recover_shardstore`` rolls the whole epoch
    forward or back atomically — never a torn cut.
"""
from __future__ import annotations

import threading
import time
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.api.mvhandle import MVStoreHandle, _MVCtx
from repro.api.substrate import SubstrateBase, Txn
from repro.core import modes as M
from repro.core.clock import AtomicInt
from repro.core.engine import AbortTx
from repro.core.engine.bulkread import as_addr_array, shard_partition
from repro.core.engine.commit import acquire_ascending
from repro.core.stats_schema import RECOVERY_STAT_KEYS, base_stats
from repro.reliability import faultpoints as FP
from repro.reliability.recovery import EpochRecord

__all__ = ["ShardStoreHandle", "shard_devices"]

_COUNTER_KEYS = ("commits", "aborts", "ro_commits", "versioned_commits")


def shard_devices(n_shards: int, mesh=None) -> List[Any]:
    """One device per shard: round-robin over the mesh's device slices.

    With an explicit mesh (``launch.mesh.make_mesh``/``make_host_mesh``)
    shards stripe over ``mesh.devices``; without one, over
    ``jax.devices()`` — and a single-device host gets ``[None] * n``
    (placement is a no-op there, the sharding still buys per-shard
    clocks)."""
    import jax
    if mesh is not None:
        from repro.launch.sharding import shard_device_slices
        return shard_device_slices(mesh, n_shards)
    devs = jax.devices()
    if len(devs) <= 1:
        return [None] * n_shards
    return [devs[s % len(devs)] for s in range(n_shards)]


class _ShardCtx:
    """Store-level transaction context: one sub-context per shard plus
    the pinned vector of shard clocks (the epoch-consistent cut)."""

    __slots__ = ("tid", "subs", "pins", "active")

    def __init__(self, tid: int, subs: List[_MVCtx]):
        self.tid = tid
        self.subs = subs
        self.pins = tuple(c.read_clock for c in subs)
        self.active = True

    @property
    def read_only(self) -> bool:
        return all(c.read_only for c in self.subs)


class ShardStoreHandle(SubstrateBase):
    name = "shardstore"

    def __init__(self, n_threads: int = 1, *, n_shards: int = 2,
                 span: int = 64, cfg=None, params=None, controller=None,
                 versioned: str = "none", start_bg: bool = True,
                 mesh=None):
        from repro.configs.base import MVStoreConfig
        from repro.configs.paper_stm import MultiverseParams
        from repro.core.mvcontroller import MVController

        assert n_shards >= 1 and span >= 1
        self.n_threads = n_threads
        self.n_shards = n_shards
        self._span = span
        self.cfg = cfg or MVStoreConfig(ring_slots=8)
        self.params = params or MultiverseParams()
        self.controller = controller or MVController(
            params=self.params, mvcfg=self.cfg, start_bg=start_bg)
        self._own_controller = controller is None
        # one solo handle per shard, all sharing ONE controller: the
        # mode cycle is global (the paper's single global mode), the
        # clocks are per shard
        self._shards = [
            MVStoreHandle(n_threads, cfg=self.cfg, params=self.params,
                          controller=self.controller, versioned=versioned)
            for _ in range(n_shards)]
        self._devices = shard_devices(n_shards, mesh)
        # the coarse level of the two-level clock: an epoch counter
        # (ticks once per cross-shard publish) and its seqlock (odd =
        # publish in flight; begin() pins only on even-and-stable)
        self._epoch = AtomicInt(0)
        self._epoch_seq = AtomicInt(0)
        self._epoch_inflight: Optional[EpochRecord] = None
        self._alloc_lock = threading.Lock()
        self._top = 0
        self._counters = [{k: 0 for k in _COUNTER_KEYS}
                          for _ in range(n_threads)]
        self._cross_commits = 0
        # durable commit log (reliability/wal.attach_wal sets this AND
        # each member shard's ``wal``/``wal_shard``): single-shard
        # commits journal through the member's solo publish; cross-shard
        # epochs journal here as one prepare-group + one group DECIDE
        self.wal = None
        self.recovery_counters = {k: 0 for k in RECOVERY_STAT_KEYS}

    # -- address routing --------------------------------------------------
    def _route(self, a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Global addresses -> (shard ids, shard-local addresses)."""
        g, n = self._span, self.n_shards
        k = a // g
        return (k % n).astype(np.int64), (k // n) * g + (a % g)

    def _route1(self, addr: int) -> Tuple[int, int]:
        g, n = self._span, self.n_shards
        k = addr // g
        return int(k % n), int((k // n) * g + (addr % g))

    def _local_top(self, s: int, top: int) -> int:
        """Shard ``s``'s heap size when the global heap has ``top`` words
        (spans round-robin, so local heaps stay contiguous prefixes)."""
        g, n = self._span, self.n_shards
        full, rem = divmod(top, g)
        local = (full // n + (1 if (full % n) > s else 0)) * g
        if full % n == s:
            local += rem
        return local

    # -- Substrate protocol ----------------------------------------------
    def begin_operation(self, tid: int) -> None:
        for sh in self._shards:
            sh.begin_operation(tid)

    def begin(self, tid: int = 0) -> Txn:
        while True:
            s0 = self._epoch_seq.load()
            if s0 & 1:
                # a cross-shard publish is mid-flight: pinning now could
                # capture half an epoch — wait the bracket out
                time.sleep(0)
                continue
            subs = [sh.begin(tid)._ctx for sh in self._shards]
            if self._epoch_seq.load() == s0:
                break
            for c in subs:          # raced the bracket: discard the pins
                c.active = False
        return Txn(self, _ShardCtx(tid, subs), tid)

    def read(self, ctx: _ShardCtx, addr: int) -> Any:
        s, local = self._route1(addr)
        try:
            return self._shards[s].read(ctx.subs[s], local)
        except AbortTx:
            self._fail(ctx)
            raise

    def read_bulk(self, ctx: _ShardCtx, addrs) -> Any:
        a = as_addr_array(addrs)
        try:
            if a.size == 0:
                return self._shards[0].read_bulk(ctx.subs[0], a)
            sid, local = self._route(a)
            if bool((sid == sid[0]).all()):     # one shard: one gather
                s = int(sid[0])
                return self._shards[s].read_bulk(ctx.subs[s], local)
            out: list = [None] * a.size
            for s, pos in shard_partition(sid, self.n_shards):
                vals = self._shards[s].read_bulk(ctx.subs[s], local[pos])
                vlist = (vals.tolist() if hasattr(vals, "tolist")
                         else list(vals))
                for p, v in zip(pos.tolist(), vlist):
                    out[p] = v
            return out
        except AbortTx:
            self._fail(ctx)
            raise

    def write(self, ctx: _ShardCtx, addr: int, value: Any) -> None:
        s, local = self._route1(addr)
        try:
            self._shards[s].write(ctx.subs[s], local, value)
        except AbortTx:
            self._fail(ctx)
            raise

    def write_bulk(self, ctx: _ShardCtx, addrs, values) -> None:
        a = as_addr_array(addrs)
        if a.size == 0:
            return
        sid, local = self._route(a)
        try:
            if bool((sid == sid[0]).all()):
                s = int(sid[0])
                self._shards[s].write_bulk(ctx.subs[s], local, values)
                return
            vlist = (values.tolist() if hasattr(values, "tolist")
                     else list(values))
            for s, pos in shard_partition(sid, self.n_shards):
                self._shards[s].write_bulk(
                    ctx.subs[s], local[pos],
                    [vlist[p] for p in pos.tolist()])
        except AbortTx:
            self._fail(ctx)
            raise

    def txn_alloc(self, ctx: _ShardCtx, n: int, init: Any = None) -> int:
        return self.alloc(n, init)

    def read_count(self, ctx: _ShardCtx) -> int:
        return sum(c.read_cnt for c in ctx.subs)

    def validate(self, ctx: _ShardCtx) -> bool:
        return all(sh.validate(c)
                   for sh, c in zip(self._shards, ctx.subs))

    # -- commit -----------------------------------------------------------
    def _touched(self, ctx: _ShardCtx) -> List[int]:
        return [s for s, c in enumerate(ctx.subs)
                if c.read_cnt or c.write_buf]

    def commit(self, txn: Txn) -> None:
        ctx = txn._ctx
        c = self._counters[ctx.tid]
        subs = ctx.subs
        write_shards = [s for s, sc in enumerate(subs) if sc.write_buf]
        touched = self._touched(ctx)
        if not write_shards:
            # read-only: each touched shard commits locally (feeding the
            # K1/K2/K3 heuristics); pins are immutable, no coordination
            for s in touched:
                self._shards[s].commit(Txn(self._shards[s], subs[s],
                                           ctx.tid))
            if any(subs[s].versioned for s in touched):
                c["versioned_commits"] += 1
            c["ro_commits"] += 1
            self._deactivate(ctx)
            return
        if len(touched) == 1:
            # the common case the ISSUE names: a single-shard footprint
            # commits with NO cross-shard coordination — the solo
            # pipeline verbatim (shard==1 bit-identity rides this path)
            s = touched[0]
            try:
                self._shards[s].commit(Txn(self._shards[s], subs[s],
                                           ctx.tid))
            except AbortTx:
                self._fail(ctx)
                raise
        else:
            self._commit_cross(ctx, touched, write_shards)
            self._cross_commits += 1
        c["commits"] += 1
        self._deactivate(ctx)

    def _commit_cross(self, ctx: _ShardCtx, touched: List[int],
                      write_shards: List[int]) -> None:
        """Two-phase epoch-stamped publish across shards.

        Phase 1 (validate): under EVERY touched shard's commit lock
        (ascending order — deadlock-free), check each shard's per-block
        stamps against this transaction's pin.  Phase 2 (publish): park
        the ``EpochRecord``, bump the epoch seqlock odd, drive each
        write shard's solo publish, even the seqlock.  Crash anywhere in
        phase 2 leaves the record for ``recover_shardstore``; the odd
        sequence keeps new pins out until recovery resolves the epoch.
        """
        subs = ctx.subs
        shards = self._shards
        if FP.ACTIVE is not None:
            FP.fire("pre_claim", ctx.tid)
        with acquire_ascending([shards[s]._commit_lock for s in touched]):
            if (self._epoch_inflight is not None
                    or any(shards[s]._check_conflict(subs[s])
                           for s in touched)):
                # fail closed on an unrecovered epoch, abort on conflict
                self._abort_cross(ctx, touched)
            if FP.ACTIVE is not None:
                FP.fire("post_claim", ctx.tid)
            rec = EpochRecord(
                epoch=self._epoch.increment(),
                write_shards=tuple(write_shards),
                pins={s: int(shards[s]._state.clock)
                      for s in write_shards},
                ctxs={s: subs[s] for s in write_shards},
                tid=ctx.tid)
            if self.wal is not None:
                # the epoch's durable twin: one PREPARE per write shard
                # (each carrying that shard's redo image + pinned clock)
                # under ONE group DECIDE — a restart replays the epoch
                # all-or-nothing across shards (wal.recover_from_wal)
                recs = []
                for s in write_shards:
                    wb = subs[s].write_buf
                    idx = sorted(wb)
                    recs.append((ctx.tid, idx, [wb[i] for i in idx],
                                 (rec.pins[s] + 1,), rec.epoch, s))
                rec.wal_lsns = tuple(self.wal.append_prepare_group(recs))
            self._epoch_inflight = rec
            self._epoch_seq.increment()        # odd: begin() waits
            try:
                if FP.ACTIVE is not None:
                    FP.fire("pre_clock_tick", ctx.tid)
                if self.wal is not None:
                    self.wal.append_decide_group(rec.wal_lsns)
                rec.publish_started = True     # the epoch commit record
                for s in write_shards:
                    # members must not re-journal solo records — the
                    # EPOCH is the durable unit
                    shards[s]._publish_locked(subs[s], wal_log=False)
                    rec.published.append(s)
                if FP.ACTIVE is not None:
                    FP.fire("pre_release", ctx.tid)
                self._epoch_inflight = None
                if self.wal is not None:
                    for lsn in rec.wal_lsns:
                        self.wal.append_complete(lsn)
            finally:
                if self._epoch_inflight is None:
                    self._epoch_seq.increment()    # even: bracket closed
                # else: crashed mid-epoch — the record stays parked and
                # the sequence odd until recover_shardstore resolves it

    # -- abort bookkeeping -------------------------------------------------
    def _deactivate(self, ctx: _ShardCtx) -> None:
        for c in ctx.subs:
            c.active = False
        ctx.active = False

    def _fail(self, ctx: _ShardCtx) -> None:
        """A shard-level abort surfaced: the shard already did its own
        accounting/heuristics; record ONE logical abort and retire every
        sub-context."""
        self._counters[ctx.tid]["aborts"] += 1
        self._deactivate(ctx)

    def _abort_cross(self, ctx: _ShardCtx, touched: List[int]) -> None:
        for s in touched:
            try:
                self._shards[s]._abort_ctx(ctx.subs[s])
            except AbortTx:
                pass
        self._fail(ctx)
        raise AbortTx()

    def abort(self, txn: Txn) -> None:
        ctx = txn._ctx
        if not getattr(ctx, "active", False):
            return
        for s in self._touched(ctx):
            if ctx.subs[s].active:
                try:
                    self._shards[s]._abort_ctx(ctx.subs[s])
                except AbortTx:
                    pass
        self._fail(ctx)

    # -- heap --------------------------------------------------------------
    def alloc(self, n: int, init: Any = None) -> int:
        with self._alloc_lock:
            base = self._top
            new_top = base + n
            for s, sh in enumerate(self._shards):
                need = self._local_top(s, new_top)
                have = self._local_top(s, base)
                if need > have:
                    got = sh.alloc(need - have, init)
                    assert got == have, (s, got, have)
                    self._place(s)
            self._top = new_top
        return base

    def _place(self, s: int) -> None:
        """Pin shard ``s``'s buffers onto its device slice (one shard =
        one device slice); no-op on a single-device host."""
        dev = self._devices[s]
        if dev is None:
            return
        import jax
        sh = self._shards[s]
        with sh._commit_lock:
            sh._install(jax.device_put(sh._state, dev))

    def peek(self, addr: int) -> Any:
        s, local = self._route1(addr)
        return self._shards[s].peek(local)

    def snapshot_bulk(self, addrs, read_clock=None):
        """``(values, ok)`` at a pinned cut.

        ``read_clock`` is ``None`` (now), one int (the same clock on
        every shard), or a per-shard vector — the pin a transaction's
        ``ctx.pins`` carries, so a recovery check can replay any epoch's
        cut."""
        a = as_addr_array(addrs)
        sid, local = self._route(a)
        out = np.zeros(a.size, np.int64)
        for s, pos in shard_partition(sid, self.n_shards):
            rc = (read_clock if read_clock is None
                  or isinstance(read_clock, (int, np.integer))
                  else read_clock[s])
            vals, ok = self._shards[s].snapshot_bulk(local[pos], rc)
            if not ok:
                return None, False
            out[pos] = np.asarray(vals)
        return out, True

    # -- accessors ---------------------------------------------------------
    @property
    def clocks(self) -> Tuple[int, ...]:
        """The per-shard clock vector (the fine level)."""
        return tuple(sh.clock for sh in self._shards)

    @property
    def clock(self) -> int:
        """Total commits across shards — one monotone scalar for callers
        that want a single progress clock."""
        return sum(self.clocks)

    @property
    def epoch(self) -> int:
        """The coarse epoch clock (ticks once per cross-shard publish)."""
        return self._epoch.load()

    # -- stats / lifecycle -------------------------------------------------
    def stats(self) -> dict:
        out = base_stats(backend=self.name,
                         mode=M.mode_name(self.controller.mode_counter))
        for c in self._counters:
            for k in _COUNTER_KEYS:
                out[k] += c[k]
        out["mode_cas"] = sum(h.stats["mode_cas"]
                              for sh in self._shards
                              for h in sh._readers)
        out["mode_transitions"] = self.controller.stats["mode_transitions"]
        out["unversioned_buckets"] = self.controller.stats[
            "blocks_unversioned"]
        out["n_shards"] = self.n_shards
        out["cross_shard_commits"] = self._cross_commits
        out["epoch"] = self.epoch
        for k, v in self.recovery_counters.items():
            out[k] += v
        return out

    def stop(self) -> None:
        if self._own_controller:
            self.controller.stop()
