#!/usr/bin/env python3
"""Bring-up smoke run of the system's main paths on one TPU chip.

    python3 chip_smoke.py                # device, kernels, tm, serve
    python3 chip_smoke.py --four-chips   # only the sharded store on 4 chips

Phases, each printing one ``phase <name> {...}`` line of its numbers
(seconds are bring-up times, compilation included where named):

  device   the first JAX device must be a TPU; otherwise the script
           exits non-zero before any other phase and prints no result;
  kernels  one seeded batch per TM kernel through its ``ops`` wrapper,
           equal to the numpy twin;
  tm       long scans beside frequent transfers on the ``multiverse``
           backend over a 2^22-word ArrayHeap, then a CommitBatcher
           burst on ``tl2`` and Mode-U ``mvstore`` commits; every
           completed scan must see the conserved sum, every device site
           must be entered and no batch may take the int64 twin route;
           prints the words each ``read_bulk`` tier resolved and the
           host bytes uploaded and copied back per kernel;
  serve    the snapshot server at full ``qwen2.5-3b`` width (random
           weights from a seed) in Mode Q, with store commits landing
           between requests;
  four     (``--four-chips`` only) a 4-shard ``ShardStoreHandle`` with
           one shard per chip against the same history on one store.

The last line of standard output is ``{"ok": true, "device": {...}}``;
a failing phase raises and ends the run with a non-zero exit.  The
phases are plain functions, so the tests run them on CPU at tiny sizes.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

INITIAL = 100          # per-word prefill of the scanned region
AMOUNT = 5             # transfer size: transfers conserve the region sum


def _report(phase: str, numbers: dict) -> None:
    print(f"phase {phase} {json.dumps(numbers, sort_keys=True)}",
          flush=True)


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(want: int) -> dict:
    """The attached devices as JAX reports them; refuses anything but
    ``want`` or more TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < want:
        raise SystemExit(f"chip_smoke: needs {want} TPU chips, found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _lock_words(rng, n: int, versions: int, n_tids: int) -> np.ndarray:
    from repro.core.engine.arrayheap import pack_lock
    from repro.core.locks import LockState
    return np.array([pack_lock(LockState(
        bool(rng.random() < 0.05), int(rng.integers(0, versions)),
        int(rng.integers(-2, n_tids)), bool(rng.random() < 0.02)))
        for _ in range(n)], np.int64)


def kernels_phase(*, heap_words: int = 1 << 22, batch: int = 1 << 16,
                  writes: int = 4096, rows: int = 4096,
                  ring_words: int = 1 << 20, seed: int = 0) -> dict:
    """Each TM kernel once, through the wrapper the engine calls, equal
    to its numpy twin.  Reports the first call (compile + run) and a
    second call of each."""
    import jax.numpy as jnp

    from repro.core.engine.validation import np_validate
    from repro.core.vlt import np_version_select
    from repro.kernels import commit_fused as cf
    from repro.kernels import ops, ref
    from repro.kernels.scatter_write import np_write_back

    rng = np.random.default_rng(seed)
    heap = rng.integers(-(1 << 30), 1 << 30, heap_words).astype(np.int32)
    out = {}

    def check(name, run, verify):
        first, t1 = _timed(run)
        verify(first)
        second, t2 = _timed(run)
        verify(second)
        out[name] = {"first_s": t1, "second_s": t2}

    # gather_read: a read_bulk chunk of random words
    addrs = rng.integers(0, heap_words, batch)
    heap_dev = jnp.asarray(heap)
    check("gather_read",
          lambda: np.asarray(ops.snapshot_read(heap_dev, addrs)),
          lambda got: np.testing.assert_array_equal(got, heap[addrs]))

    # scatter_write: a donated row publish of unique addresses
    w_addr = rng.choice(heap_words, writes, replace=False)
    w_val = rng.integers(-(1 << 30), 1 << 30, writes).astype(np.int32)
    want_row = np_write_back(heap, w_addr, w_val)
    check("scatter_write",
          lambda: np.asarray(ops.publish_row(jnp.asarray(heap), w_addr,
                                             w_val)),
          lambda got: np.testing.assert_array_equal(got, want_row))

    # validate: clean read sets pass, one stale entry fails, per mode
    n = rows
    ver = rng.integers(0, 1000, n)
    own = np.full(n, -1, np.int32)
    meta = np.zeros(n, np.int32)
    cases = []
    for mode in (0, 1, 2):
        for stale in (False, True):
            v = ver.copy()
            if stale:
                v[int(rng.integers(n))] = 5000
            cases.append((mode, v))

    def validate_all():
        return [ops.validate_readset(v, own, meta, ver, 2000, 0, mode)
                for mode, v in cases]

    def verify_validate(got):
        want = [np_validate(v, own, meta, ver, 2000, 0, mode)
                for mode, v in cases]
        assert got == want == [True, False] * 3, (got, want)

    check("validate", validate_all, verify_validate)

    # version_select: newest-first mirror rows at three clocks
    ts = np.sort(rng.integers(0, 1000, (rows, 4)), axis=1)[:, ::-1].copy()
    data = rng.integers(-(1 << 30), 1 << 30, (rows, 4))

    def select_all():
        return [ops.version_select(ts, data, c) for c in (1, 500, 999)]

    def verify_select(got):
        for (vals, ok), c in zip(got, (1, 500, 999)):
            want_v, want_ok = np_version_select(ts, data, c)
            np.testing.assert_array_equal(ok, want_ok)
            np.testing.assert_array_equal(vals[ok], want_v[want_ok])

    check("version_select", select_all, verify_select)

    # snapshot_select: a two-slot ring, each slot and none selected
    ring = jnp.asarray(rng.integers(-(1 << 30), 1 << 30, (2, ring_words)),
                       jnp.int32)
    ring_ts = jnp.asarray([3, 7], jnp.int32)
    clocks = (2, 5, 9)

    def snap_all():
        return [ops.snapshot_select(ring, ring_ts, jnp.int32(c))
                for c in clocks]

    def verify_snap(got):
        for (val, ok), c in zip(got, clocks):
            want, want_ok = ref.snapshot_select_ref(ring, ring_ts, c)
            assert bool(ok) == bool(want_ok), c
            if bool(want_ok):
                np.testing.assert_array_equal(np.asarray(val),
                                              np.asarray(want))

    check("snapshot_select", snap_all, verify_snap)

    # commit_fused: a group of disjoint members with read/lock entries,
    # some of which fail their verdict
    T, per = 32, writes // 32
    from repro.core.engine.arrayheap import unpack_words
    seg_w = np.repeat(np.arange(T), per)
    l_words = _lock_words(rng, 2 * T, 100, T)
    l_seg = rng.integers(0, T, 2 * T)
    r_words = _lock_words(rng, 8 * T, 100, T)
    r_seg = rng.integers(0, T, 8 * T)
    r_seen = unpack_words(r_words)[0]
    tids = np.arange(T)
    r_clocks = rng.integers(50, 100, T)

    want_heap, want_ok, want_lver = cf.np_commit_fused(
        heap, w_addr, w_val, seg_w, *unpack_words(l_words), l_seg,
        *unpack_words(r_words), r_seen, r_seg, tids, r_clocks, 200, T,
        cf.MODE_LE)
    assert 0 < int(want_ok.sum()) < T, "the batch must mix verdicts"

    def fused():
        got = ops.commit_fused(jnp.asarray(heap), w_addr, w_val, seg_w,
                               l_words, l_seg, r_words, r_seen, r_seg,
                               tids, r_clocks, 200, T, mode=cf.MODE_LE)
        return np.asarray(got[0]), got[1], got[2]

    def verify_fused(got):
        np.testing.assert_array_equal(got[0], want_heap)
        np.testing.assert_array_equal(got[1], want_ok)
        np.testing.assert_array_equal(unpack_words(got[2])[0], want_lver)

    check("commit_fused", fused, verify_fused)
    return out


# ---------------------------------------------------------------------------
# tm
# ---------------------------------------------------------------------------


def tm_phase(*, heap_words: int = 1 << 22, region: int = 1 << 20,
             chunk: int = 1 << 16, wide: int = 512, seconds: float = 10.0,
             group_rounds: int = 20, group_size: int = 32,
             mv_words: int = 1 << 20, mv_commits: int = 300,
             seed: int = 0) -> dict:
    """The paper's workload on the word-level TM, then the two batched
    commit paths.  Raises AssertionError on any violation."""
    from repro.api import MaxRetriesExceeded, make_tm, run
    from repro.configs.paper_stm import MultiverseParams
    from repro.core.engine.groupcommit import CommitBatcher
    from repro.kernels import ops

    ops.COUNTS.reset()
    out = {}

    # -- long scans beside transfers (multiverse) ------------------------
    params = MultiverseParams(k1=2, k2=3, k3=3, lock_table_bits=16)
    tm, t_setup = _timed(lambda: make_tm("multiverse", 4, params=params,
                                         array_heap=True))
    base = tm.alloc(heap_words, INITIAL)
    lo = base + (heap_words - region) // 2          # the scanned region
    expected = region * INITIAL
    stop = threading.Event()
    counts = {"scans": 0, "violations": 0, "failed_scans": 0,
              "updates": 0, "wide_updates": 0, "failed_updates": 0}
    lock = threading.Lock()
    errors = []

    def bump(key, n=1):
        with lock:
            counts[key] += n

    def scanner(tid):
        def scan_tx(tx):
            tot = 0
            for off in range(0, region, chunk):
                vals = tx.read_bulk(range(lo + off, lo + off + chunk))
                tot += int(np.asarray(vals, np.int64).sum())
            return tot
        while not stop.is_set():
            try:
                tot = run(tm, scan_tx, tid=tid, max_retries=200)
            except MaxRetriesExceeded:
                bump("failed_scans")
                continue
            bump("scans")
            if tot != expected:
                bump("violations")

    def updater(tid):
        r = random.Random(seed * 10007 + tid)

        def transfer(tx):
            i, j = r.sample(range(region), 2)
            a, b = tx.read(lo + i), tx.read(lo + j)
            tx.write(lo + i, a - AMOUNT)
            tx.write(lo + j, b + AMOUNT)

        def wide_transfer(tx):
            # a read set past BULK_MIN: commit revalidates it in bulk
            off = r.randrange(region - wide)
            vals = np.asarray(tx.read_bulk(range(lo + off,
                                                 lo + off + wide)))
            i, j = r.sample(range(wide), 2)
            tx.write(lo + off + i, int(vals[i]) - AMOUNT)
            tx.write(lo + off + j, int(vals[j]) + AMOUNT)

        n = 0
        while not stop.is_set():
            wide_turn = tid == 3 and n % 4 == 0
            try:
                run(tm, wide_transfer if wide_turn else transfer, tid=tid,
                    max_retries=2000)
            except MaxRetriesExceeded:
                bump("failed_updates")
                continue
            bump("wide_updates" if wide_turn else "updates")
            n += 1

    def guard(fn, tid):
        try:
            fn(tid)
        except BaseException as e:          # surfaced after the join
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=guard, args=(scanner, 0))]
    threads += [threading.Thread(target=guard, args=(updater, t))
                for t in (1, 2, 3)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    # run ``seconds``, then on until one scan completed (bounded)
    time.sleep(seconds)
    deadline = time.perf_counter() + 20 * seconds
    while counts["scans"] == 0 and not errors \
            and time.perf_counter() < deadline:
        time.sleep(0.05)
    stop.set()
    for th in threads:
        th.join(timeout=600)
        assert not th.is_alive(), "a tm worker did not stop"
    t_run = time.perf_counter() - t0
    if errors:
        raise errors[0]
    final = int(np.asarray(tm.raw.heap.gather(
        np.arange(lo, lo + region))).sum())
    stats = tm.stats()
    raw = tm.raw.stats()
    tm.stop()
    assert counts["violations"] == 0, counts
    assert counts["scans"] >= 1, counts
    assert final == expected, (final, expected)
    # words the scans' read_bulk tiers resolved: batch, mirror, scalar
    tiers = {k: raw[k] for k in ("bulk_batch_words", "version_gather_hits",
                                 "bulk_scalar_words")}
    out["scan"] = dict(counts, setup_s=t_setup, run_s=t_run,
                       mode_transitions=stats.get("mode_transitions", 0),
                       **tiers)

    # -- CommitBatcher burst (tl2): groups through ops.commit_fused ------
    g = make_tm("tl2", group_size, params=params, array_heap=True)
    gbase = g.alloc(heap_words, 0)
    raw = g.raw
    words = 8
    model = {}
    rng = np.random.default_rng(seed)
    gstats = {"grouped": 0, "solo": 0, "groups": 0, "failed": 0}
    t0 = time.perf_counter()
    for rnd in range(group_rounds):
        spots = rng.choice(heap_words // words, group_size, replace=False)
        batcher = CommitBatcher(raw)

        def member(tx, rnd=rnd, t=0, a0=0):
            seen = tx.read(a0)                 # a read set for the verdict
            for k in range(words):
                v = rnd * 1000 + t * words + k + int(seen)
                tx.write(a0 + k, v)
                model[a0 + k] = v

        spots = [gbase + int(s) * words for s in spots]
        for t, a0 in enumerate(spots):
            tx = raw.begin(t)
            member(tx, t=t, a0=a0)
            batcher.add(tx)
        ok = batcher.commit_all()
        # a member whose read word shares a lock word with another
        # member's write aborts (lock-table aliasing); retry it alone
        for t, a0 in enumerate(spots):
            if not ok[t]:
                run(g, lambda tx, t=t, a0=a0: member(tx, t=t, a0=a0),
                    tid=t, max_retries=10)
        for k, v in batcher.stats.items():
            gstats[k] += v
    t_groups = time.perf_counter() - t0
    addr = np.fromiter(model, np.int64)
    got = np.asarray(raw.heap.gather(addr))
    np.testing.assert_array_equal(got, np.fromiter(model.values(),
                                                   np.int64))
    g.stop()
    assert gstats["groups"] >= 1, gstats
    out["groups"] = dict(gstats, run_s=t_groups)

    # -- Mode-U MVStore commits: the fused ring publish ------------------
    mv = make_tm("mvstore", 1, params=params, forced_mode="U",
                 versioned="all", ring_slots=8, start_bg=False)
    mbase = mv.alloc(mv_words, 0)
    mmodel = np.zeros(mv_words, np.int64)
    t0 = time.perf_counter()
    for c in range(mv_commits):
        idx = rng.choice(mv_words, 4, replace=False)
        vals = rng.integers(0, 1 << 30, 4)

        def write(tx, idx=idx, vals=vals):
            tx.write_bulk(mbase + idx, vals)
        run(mv, write, tid=0, max_retries=10)
        mmodel[idx] = vals
    t_mv = time.perf_counter() - t0
    got, ok = mv.snapshot_bulk(np.arange(mbase, mbase + mv_words))
    assert ok
    np.testing.assert_array_equal(np.asarray(got, np.int64), mmodel)
    mstats = mv.stats()
    mv.stop()
    assert mstats["commits"] == mv_commits, mstats
    out["mvstore"] = {"commits": mstats["commits"], "run_s": t_mv}

    out["site_entries"] = dict(ops.COUNTS.entries)
    out["int64_twin_routes"] = dict(ops.COUNTS.twin_routes)
    out["h2d_bytes"] = dict(ops.COUNTS.h2d_bytes)
    out["d2h_bytes"] = dict(ops.COUNTS.d2h_bytes)
    for site in ("gather_read", "validate", "version_select",
                 "commit_fused"):
        assert ops.COUNTS.entries[site] >= 1, (site, out["site_entries"])
    assert not ops.COUNTS.twin_routes, out["int64_twin_routes"]
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_phase(cfg=None, *, slots: int = 4, requests: int = 8,
                prompt_len: int = 128, gen: int = 16, seed: int = 0
                ) -> dict:
    """The snapshot server at full model width in Mode Q.  Twice, while
    requests are half decoded, the store takes a commit (the trainer's
    publish, its buffers donated): those requests must abort, re-pin at
    the new clock and still complete."""
    import jax

    from repro.configs import MVStoreConfig, get_config
    from repro.core import mvstore
    from repro.launch.serve import Server
    from repro.serve.queue import Outcome

    cfg = cfg or get_config("qwen2.5-3b")
    mvcfg = MVStoreConfig(mode="Q")
    server, t_init = _timed(lambda: Server(
        cfg, batch=slots, prompt_len=prompt_len,
        max_len=prompt_len + gen, mvcfg=mvcfg, seed=seed))
    after_init = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    commit = jax.jit(
        lambda st: mvstore.mv_commit(st, st.live, local_mode="Q",
                                     cfg=mvcfg),
        donate_argnums=(0,))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (requests, prompt_len),
                           dtype=np.int32)
    reqs = [server.submit(p, gen) for p in prompts]
    commits, done, armed = 0, 0, True
    first_token_s = None
    t0 = time.perf_counter()
    while any(r.outcome is Outcome.PENDING for r in reqs):
        if not server.pump():
            time.sleep(1e-4)
        if first_token_s is None and any(r.tokens for r in reqs):
            first_token_s = time.perf_counter() - t0
        if armed and commits < 2 and any(
                r.outcome is Outcome.PENDING
                and gen // 2 <= len(r.tokens) < gen for r in reqs):
            server.mv_state = commit(server.mv_state)
            commits, armed = commits + 1, False
        now_done = sum(r.outcome is not Outcome.PENDING for r in reqs)
        armed = armed or now_done > done     # next commit after a finish
        done = now_done
    t_serve = time.perf_counter() - t0
    completed = [r for r in reqs if r.outcome is Outcome.COMPLETED]
    assert len(completed) == requests, [r.outcome for r in reqs]
    toks = np.asarray([r.tokens[:gen] for r in completed])
    assert toks.shape == (requests, gen), toks.shape
    assert ((toks >= 0) & (toks < cfg.vocab_size)).all()
    assert commits >= 1 and server.aborts >= 1, (commits, server.aborts)
    n_params = sum(x.size for x in jax.tree.leaves(server.mv_state.live))
    stats = jax.devices()[0].memory_stats() or {}
    return {"arch": cfg.name, "params": int(n_params),
            "requests": requests, "completed": len(completed),
            "tokens": int(toks.size), "commits": commits,
            "aborts": server.aborts, "init_s": t_init,
            "first_token_s": first_token_s, "serve_s": t_serve,
            "bytes_in_use_after_init": after_init,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips_phase(*, n_shards: int = 4, words: int = 1 << 16,
                     n_ops: int = 200, seed: int = 0) -> dict:
    """A seeded history of single- and cross-shard commits on a
    ``n_shards``-shard store, one shard per device, against the same
    history on one ``mvstore``: the final heaps must be equal."""
    import jax

    from repro.api import make_tm, run
    from repro.configs.paper_stm import MultiverseParams
    from repro.core.shardstore import ShardStoreHandle

    params = MultiverseParams(k1=2, k2=50, k3=50, lock_table_bits=12)
    span = 256
    r = np.random.RandomState(seed)
    history = []
    for _ in range(n_ops):
        lo = int(r.randint(words - 2 * span))
        ln = int(r.randint(2, 2 * span))        # up to two spans: cross
        history.append((lo, ln, int(r.randint(1 << 30))))

    def drive(tm):
        base = tm.alloc(words, 7)
        for lo, ln, v in history:
            def one(tx, lo=lo, ln=ln, v=v):
                head = np.asarray(tx.read_bulk(range(base + lo,
                                                     base + lo + 2)))
                tx.write_bulk(range(base + lo, base + lo + ln),
                              np.arange(ln) + v + int(head.sum()) % 97)
            run(tm, one, tid=0, max_retries=10)
        with tm.txn(tid=0) as tx:
            return np.asarray(tx.read_bulk(range(base, base + words)),
                              np.int64)

    st = ShardStoreHandle(1, n_shards=n_shards, span=span, params=params,
                          start_bg=False)
    got, t_sharded = _timed(lambda: drive(st))
    placed = [sh.state.live["heap"].devices() for sh in st._shards]
    cross = st.stats()["cross_shard_commits"]
    st.stop()
    solo = make_tm("mvstore", 1, params=params, start_bg=False)
    want, t_solo = _timed(lambda: drive(solo))
    solo.stop()
    homes = [next(iter(d)) for d in placed]
    assert all(len(d) == 1 for d in placed), placed
    assert len(set(homes)) == min(n_shards, len(jax.devices())), homes
    assert cross >= 1, "the history must commit across shards"
    np.testing.assert_array_equal(got, want)
    return {"shards": n_shards, "devices": [str(d) for d in homes],
            "ops": n_ops, "cross_commits": int(cross), "words": words,
            "sharded_s": t_sharded, "solo_s": t_solo}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard store across 4 chips")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import use_compile_cache
    cache = use_compile_cache()
    device = device_phase(4 if args.four_chips else 1)
    _report("device", dict(device, compile_cache=cache))
    if args.four_chips:
        _report("four", four_chips_phase())
    else:
        _report("kernels", kernels_phase())
        _report("tm", tm_phase())
        _report("serve", serve_phase())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
