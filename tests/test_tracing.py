"""Program spans (``repro.runtime.tracing``) and the versioned store's
abort reasons: no annotation object while no profiler records, spans
nested by thread with their args inside a recorded trace, and every
abort counted under exactly one reason."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.api import AbortTx, make_tm, run
from repro.api.mvhandle import ABORT_REASONS
from repro.configs.paper_stm import MultiverseParams
from repro.runtime import tracing


def _store(n_threads=2, **kw):
    kw.setdefault("ring_slots", 2)
    return make_tm("mvstore", n_threads, start_bg=False,
                   params=MultiverseParams(k1=2, k2=50, k3=50), **kw)


def test_span_without_a_profiler_builds_no_annotation(monkeypatch):
    built = []
    monkeypatch.setattr(tracing, "_On",
                        lambda *a, **k: built.append(a) or None)
    tm = _store(1)
    a = tm.alloc(4, 1)
    run(tm, lambda tx: tx.write(a, tx.read(a + 1) + 1), tid=0)
    assert run(tm, lambda tx: list(tx.read_bulk(range(a, a + 4))),
               tid=0) == [2, 1, 1, 1]
    tm.stop()
    sp = tracing.span("tm.attempt", tid=0)
    with sp as inner:
        inner.set(outcome="commit")
    assert sp is tracing.span("mvstore.read") and built == []


def _recorded(tmp_path, body):
    """Host-plane events ``{line: [(name, start, end, args)]}`` of a
    trace recorded around ``body()``."""
    with jax.profiler.trace(str(tmp_path)):
        body()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events
                       if e.name.startswith(("tm.", "mvstore."))]
                if evs:
                    lines[(plane.name, i)] = evs
    return lines


def test_spans_nest_by_thread_with_their_args(tmp_path):
    tm = _store(3)
    a = tm.alloc(8, 10)
    ready = threading.Barrier(2)

    def transfer(tid):
        ready.wait(timeout=30)
        run(tm, lambda tx: tx.write(a + tid, tx.read(a + tid) - 1),
            tid=tid)

    def body():
        ths = [threading.Thread(target=transfer, args=(t,))
               for t in (1, 2)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=60)
            assert not th.is_alive()

    lines = _recorded(tmp_path, body)
    tids = set()
    for evs in lines.values():
        attempts = [e for e in evs if e[0] == "tm.attempt"]
        reads = [e for e in evs if e[0] == "mvstore.read"]
        assert attempts and reads
        tid = attempts[0][3]["tid"]
        tids.add(tid)
        for name, s, e, args in attempts:
            assert args["tid"] == tid and args["attempt"] >= 1
            assert args["outcome"] == "commit" or \
                args["outcome"].startswith("abort.")
        assert attempts[-1][3]["outcome"] == "commit"
        assert len({x[3]["op"] for x in attempts}) == 1
        # each read and publish lies inside one attempt of its thread
        for name, s, e, args in evs:
            if name != "tm.attempt":
                assert any(a_s <= s and e <= a_e
                           for _, a_s, a_e, _ in attempts), name
        assert {x[3]["path"] for x in reads} == {"live"}
        assert any(x[0] == "mvstore.publish" and x[3]["words"] == 1
                   for x in evs)
    assert tids == {1, 2}
    tm.stop()


def test_an_aborted_attempt_names_its_reason(tmp_path):
    tm = _store(2)
    a = tm.alloc(2, 0)
    first = [True]

    def body_fn(tx):
        v = tx.read(a)
        if first[0]:
            first[0] = False
            run(tm, lambda t2: t2.write(a + 1, 5), tid=1)
        tx.write(a, v + 1)

    lines = _recorded(tmp_path, lambda: run(tm, body_fn, tid=0))
    outcomes = [e[3]["outcome"] for evs in lines.values() for e in evs
                if e[0] == "tm.attempt" and e[3]["tid"] == 0]
    assert outcomes == ["abort.commit_conflict", "commit"]
    tm.stop()


def _force(tm, a, reason):
    """Make one transaction of ``tm`` abort for ``reason``."""
    if reason == "clock":
        tx = tm.begin(0)
        tx.read(a)
        run(tm, lambda t: t.write(a, 1), tid=1)
        tx.read(a)
    elif reason == "ring_window":
        tm._readers[0].versioned = True
        tx = tm.begin(0)
        for v in range(3):                   # past the 2-slot ring
            run(tm, lambda t, v=v: t.write(a, v), tid=1)
        tx.read(a)
    elif reason == "donation_race":
        tx = tm.begin(0)
        clock, live, ring, ts = tm._snap
        dead = jnp.array(live)
        dead.delete()
        tm._snap = (clock, dead, ring, ts)
        try:
            tx.read(a)
        finally:
            tm._snap = (clock, live, ring, ts)
    elif reason == "commit_conflict":
        tx = tm.begin(0)
        tx.write(a, 7)
        run(tm, lambda t: t.write(a + 1, 1), tid=1)
        tm.commit(tx)
    elif reason == "versioned_write":
        tm._readers[0].versioned = True
        tm.begin(0).write(a, 3)
    else:                                    # explicit: the body raised
        run(tm, lambda t: t.read(a) / 0, tid=0)


def test_each_abort_reason_is_counted_once():
    tm = _store(2, forced_mode="U", versioned="all")
    a = tm.alloc(4, 0)
    for reason in ABORT_REASONS:
        raised = ZeroDivisionError if reason == "explicit" else AbortTx
        with pytest.raises(raised) as e:
            _force(tm, a, reason)
        assert getattr(e.value, "reason", "explicit") == reason
        tm._readers[0].versioned = False
        st = tm.stats()
        assert st["abort_reasons"][reason] == 1, (reason, st)
        assert sum(st["abort_reasons"].values()) == st["aborts"]
    assert set(st["abort_reasons"].values()) == {1}
    tm.stop()


def test_shard_store_counts_one_reason_per_logical_abort():
    tm = make_tm("shardstore", 2, n_shards=2, start_bg=False,
                 params=MultiverseParams(k1=2, k2=50, k3=50))
    a = tm.alloc(8, 0)
    tx = tm.begin(0)
    tx.read(a)
    run(tm, lambda t: t.write(a, 1), tid=1)
    with pytest.raises(AbortTx):
        tx.read(a)
    st = tm.stats()
    assert st["abort_reasons"]["clock"] == 1
    assert sum(st["abort_reasons"].values()) == st["aborts"] == 1
    tm.stop()
