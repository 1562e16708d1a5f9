"""The bank workload kind: TinySTM's ``bank`` test on a backend of
``repro.api``.

A configuration of this kind (``"kind": "bank"``) gives the accounts
(``bank``: ``accounts``, ``opening_balance``, ``transfer_amount``), the
system under test (``system``: ``backend``, ``options``, ``params`` of
``make_tm``) and the accounts loaded per commit (``load_chunk``).  A
traffic file (``bench/traffic/<name>.json``) gives the parameters of
the one generator here:

  transfer_threads   threads doing 2-account transfers of a fixed amount
  auditors           threads each running audits back to back
  audit_chunk        accounts per ``read_bulk`` call of an audit
                     (0: the whole bank in one call)
  transfer_retries   attempts before a transfer counts as failed
  audit_retries      attempts before an audit counts as failed
  warmup_audits      audits committed before the window may open
  warmup_transfers   transfers committed before the window may open
  warmup_seconds     least time the traffic runs before the window

Every thread is a closed loop: it starts its next operation when
``run()`` returned the last one.  The pairs a transfer thread moves money
between come from the seed, drawn per thread in a fixed order, so a seed
fixes every thread's sequence of operations.  The retry loop is the
system's own ``run``; the generator only counts attempts (body calls)
and takes host-clock spans around the calls it makes.  The plain
reference that decides ``correct`` is ``bench/reference.py``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable, List, Optional

import numpy as np

from measure import in_window, percentile, rate
from reference import (ReferenceBank, RetriesExhausted, audit_bad_accounts,
                       final_bad_accounts, initial_balances)

now = time.perf_counter


@dataclass
class Traffic:
    transfer_threads: int
    auditors: int = 0
    audit_chunk: int = 0
    transfer_retries: int = 2000
    audit_retries: int = 200
    warmup_audits: int = 0
    warmup_transfers: int = 0
    warmup_seconds: float = 0.0

    def chunks(self, accounts: int) -> List[tuple]:
        """The ``[lo, hi)`` account ranges one audit reads, in order."""
        step = self.audit_chunk or accounts
        return [(lo, min(lo + step, accounts))
                for lo in range(0, accounts, step)]


def parse_traffic(params: dict) -> Traffic:
    known = set(Traffic.__dataclass_fields__)
    extra = set(params) - known - {"why"}
    if extra:
        raise ValueError(f"unknown traffic keys {sorted(extra)}")
    return Traffic(**{k: v for k, v in params.items() if k in known})


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class ProgramSystem:
    """The configured backend behind ``repro.api``, holding the opening
    balances: what the window drives.  ``run`` is the system's own retry
    loop."""

    def __init__(self, config: dict, balances: np.ndarray,
                 n_threads: int):
        from repro.api import MaxRetriesExceeded, make_tm, run
        from repro.configs.paper_stm import MultiverseParams

        self._run = run
        self._exhausted = MaxRetriesExceeded
        sysconf = config["system"]
        params = MultiverseParams(**sysconf.get("params", {}))
        self.tm = make_tm(sysconf["backend"], n_threads, params=params,
                          **sysconf.get("options", {}))
        n = balances.shape[0]
        self.base = self.tm.alloc(n, 0)
        step = int(config.get("load_chunk", n))
        for lo in range(0, n, step):
            hi = min(lo + step, n)

            def load(tx, lo=lo, hi=hi):
                tx.write_bulk(range(self.base + lo, self.base + hi),
                              balances[lo:hi])
            run(self.tm, load, tid=0)
        self.accounts = n

    def run(self, fn, tid: int = 0, max_retries: int = 0):
        try:
            return self._run(self.tm, fn, tid=tid, max_retries=max_retries)
        except self._exhausted as e:
            raise RetriesExhausted(str(e)) from None

    def read_all(self, chunk: int = 1 << 18) -> np.ndarray:
        """Every balance, by read-only transactions once traffic stopped."""
        def read(tx):
            return np.concatenate([
                np.asarray(tx.read_bulk(range(self.base + lo, self.base
                                              + min(lo + chunk,
                                                    self.accounts))),
                           np.int64)
                for lo in range(0, self.accounts, chunk)])
        return self.run(read, tid=0)

    def stats(self) -> dict:
        return self.tm.stats()

    def stop(self) -> None:
        self.tm.stop()


def control():
    """The control's system factory: the plain reference bank in the
    system's place, acknowledging each transfer before it applies it."""
    def make(config, balances, n_threads):
        return ReferenceBank(balances, write_behind=True)
    return make


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


@dataclass
class ThreadLog:
    """What one thread did, appended only by that thread."""

    # committed transfers: start, end, body end of the last attempt
    t_start: List[float] = field(default_factory=list)
    t_end: List[float] = field(default_factory=list)
    t_body_end: List[float] = field(default_factory=list)
    src: List[int] = field(default_factory=list)
    dst: List[int] = field(default_factory=list)
    attempts: List[int] = field(default_factory=list)
    failed_at: List[float] = field(default_factory=list)
    # committed audits, and the start and accounts of each read_bulk
    audits: List[dict] = field(default_factory=list)
    chunks: List[tuple] = field(default_factory=list)


class Bank:
    """Runs one cell's traffic against a system.

    ``system`` has ``run(fn, tid, max_retries)`` raising
    ``RetriesExhausted`` when out of attempts, ``base`` (the address of
    account 0) and ``read_all()``.  ``annotate(name)`` returns a context
    manager that marks a host span in the profiler's trace.
    """

    def __init__(self, system, traffic: Traffic, *, accounts: int,
                 amount: int, seed: int,
                 annotate: Optional[Callable] = None):
        self.system = system
        self.traffic = traffic
        self.accounts = accounts
        self.amount = amount
        self.seed = abs(int(seed))
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.stop = threading.Event()
        n = traffic.auditors + traffic.transfer_threads
        self.logs = [ThreadLog() for _ in range(n)]
        self.errors: List[BaseException] = []
        self._threads: List[threading.Thread] = []

    # -- operations -------------------------------------------------------
    def _pairs(self, tid: int, block: int = 4096):
        rng = np.random.default_rng([self.seed, 0x7472616E, tid])
        n = self.accounts
        while True:
            i = rng.integers(0, n, block)
            j = rng.integers(0, n - 1, block)
            j = j + (j >= i)
            yield from zip(i.tolist(), j.tolist())

    def _transfer_loop(self, tid: int) -> None:
        log = self.logs[tid]
        base, amount = self.system.base, self.amount
        retries = self.traffic.transfer_retries
        body_end = [0.0]
        tries = [0]
        pairs = self._pairs(tid)
        while not self.stop.is_set():
            i, j = next(pairs)
            src, dst = base + i, base + j

            def body(tx, src=src, dst=dst):
                tries[0] += 1
                a = tx.read(src)
                b = tx.read(dst)
                tx.write(src, a - amount)
                tx.write(dst, b + amount)
                body_end[0] = now()

            tries[0] = 0
            t0 = now()
            try:
                with self.annotate("bench.transfer"):
                    self.system.run(body, tid=tid, max_retries=retries)
            except RetriesExhausted:
                log.failed_at.append(now())
                continue
            t1 = now()
            log.t_start.append(t0)
            log.t_end.append(t1)
            log.t_body_end.append(body_end[0])
            log.src.append(i)
            log.dst.append(j)
            log.attempts.append(tries[0])

    def _audit_loop(self, tid: int) -> None:
        log = self.logs[tid]
        base = self.system.base
        chunks = self.traffic.chunks(self.accounts)
        retries = self.traffic.audit_retries
        tries = [0]

        def body(tx):
            tries[0] += 1
            got = []
            for lo, hi in chunks:
                t = now()
                with self.annotate("bench.audit_chunk"):
                    vals = np.asarray(
                        tx.read_bulk(range(base + lo, base + hi)), np.int64)
                log.chunks.append((t, hi - lo))
                got.append(vals)
            return got

        while not self.stop.is_set():
            tries[0] = 0
            t0 = now()
            try:
                with self.annotate("bench.audit"):
                    got = self.system.run(body, tid=tid,
                                          max_retries=retries)
            except RetriesExhausted:
                log.failed_at.append(now())
                continue
            log.audits.append({"t_start": t0, "t_end": now(),
                               "attempts": tries[0], "values": got})

    # -- lifecycle --------------------------------------------------------
    def _guard(self, fn, tid: int) -> None:
        try:
            fn(tid)
        except BaseException as e:          # surfaced by join()
            self.errors.append(e)
            self.stop.set()

    def start(self) -> None:
        t = self.traffic
        loops = [self._audit_loop] * t.auditors
        loops += [self._transfer_loop] * t.transfer_threads
        for tid, fn in enumerate(loops):
            th = threading.Thread(target=self._guard, args=(fn, tid),
                                  name=f"bench-{fn.__name__}-{tid}",
                                  daemon=True)
            self._threads.append(th)
        for th in self._threads:
            th.start()

    def committed(self) -> tuple:
        """(transfers, audits) committed so far (racy; for warm-up)."""
        return (sum(len(g.t_end) for g in self.logs),
                sum(len(g.audits) for g in self.logs))

    def wait_warm(self, timeout_s: float) -> None:
        """Return once the warm-up asked for has run; raise if the
        traffic fails or does not warm up within ``timeout_s``."""
        t = self.traffic
        t0 = now()
        while True:
            if self.errors:
                raise self.errors[0]
            transfers, audits = self.committed()
            if (now() - t0 >= t.warmup_seconds
                    and transfers >= t.warmup_transfers
                    and audits >= t.warmup_audits):
                return
            if now() - t0 > timeout_s:
                raise TimeoutError(
                    f"warm-up incomplete after {timeout_s} s: "
                    f"{transfers} transfers, {audits} audits")
            time.sleep(0.01)

    def join(self, timeout_s: float) -> None:
        self.stop.set()
        deadline = now() + timeout_s
        for th in self._threads:
            th.join(timeout=max(0.0, deadline - now()))
        alive = [th.name for th in self._threads if th.is_alive()]
        if alive:
            raise TimeoutError(f"traffic threads did not stop: {alive}")
        if self.errors:
            raise self.errors[0]

    # -- records ----------------------------------------------------------
    def transfers(self) -> dict:
        """Every committed transfer, as arrays."""
        cat = lambda k, dt: np.asarray(  # noqa: E731
            [x for g in self.logs for x in getattr(g, k)], dt)
        return {"t_start": cat("t_start", np.float64),
                "t_end": cat("t_end", np.float64),
                "t_body_end": cat("t_body_end", np.float64),
                "src": cat("src", np.int64), "dst": cat("dst", np.int64),
                "attempts": cat("attempts", np.int64)}

    def audits(self) -> List[dict]:
        return sorted((a for g in self.logs for a in g.audits),
                      key=lambda a: a["t_end"])

    def failed_at(self) -> np.ndarray:
        return np.asarray([x for g in self.logs for x in g.failed_at])

    def chunks(self) -> np.ndarray:
        """``[start, accounts]`` of every completed read_bulk."""
        return np.asarray([s for g in self.logs for s in g.chunks],
                          np.float64).reshape(-1, 2)


# ---------------------------------------------------------------------------
# the workload, as the harness drives it
# ---------------------------------------------------------------------------


class Workload:
    """One bank cell: the system loaded with the seed's opening balances
    and the traffic that runs against it."""

    def __init__(self, config: dict, traffic: Traffic, *, seed: int,
                 annotate=None, make_system=None):
        bank = config["bank"]
        self.accounts = bank["accounts"]
        self.amount = bank["transfer_amount"]
        self.init = initial_balances(seed, self.accounts,
                                     *bank["opening_balance"])
        n_threads = traffic.auditors + traffic.transfer_threads
        self.system = (make_system or ProgramSystem)(config, self.init,
                                                     n_threads)
        self.bank = Bank(self.system, traffic, accounts=self.accounts,
                         amount=self.amount, seed=seed, annotate=annotate)
        self.final = None

    def start(self) -> None:
        self.bank.start()

    def wait_warm(self, timeout_s: float) -> None:
        self.bank.wait_warm(timeout_s)

    def progress(self) -> str:
        transfers, audits = self.bank.committed()
        return f"{transfers} transfers, {audits} audits committed"

    def stop(self) -> None:
        self.bank.stop.set()

    def finish(self, timeout_s: float) -> dict:
        """Wait for the traffic to end, read the final state; returns the
        system's counters."""
        self.bank.join(timeout_s)
        self.final = self.system.read_all()
        return self.system.stats() if hasattr(self.system, "stats") else {}

    def free(self) -> None:
        self.system.stop()
        self.system = self.bank.system = None

    def record(self, t0: float, t1: float, stats: dict) -> SimpleNamespace:
        """What the metric readers see of the window ``[t0, t1]``."""
        tr = self.bank.transfers()
        t_in = in_window(tr["t_end"], t0, t1)
        a_in = [a for a in self.bank.audits() if t0 <= a["t_end"] <= t1]
        f_in = int(in_window(self.bank.failed_at(), t0, t1).sum())
        chunks = self.bank.chunks()
        c_in = in_window(chunks[:, 0], t0, t1)
        return SimpleNamespace(
            transfers={k: v[t_in] for k, v in tr.items()}, audits=a_in,
            chunk_words=int(chunks[c_in, 1].sum()), stats=stats,
            attempted=int(t_in.sum()) + len(a_in) + f_in, failed=f_in)

    def end_to_end(self, t0: float, t1: float) -> dict:
        tr = self.bank.transfers()
        done = in_window(tr["t_end"], t0, t1)
        lat_ms = (tr["t_end"][done] - tr["t_start"][done]) * 1e3
        # each committed audit's accounts, spread evenly over the time from
        # its run() call to its return: the share inside the window counts
        words = 0.0
        for a in self.bank.audits():
            inside = min(a["t_end"], t1) - max(a["t_start"], t0)
            if inside > 0:
                n = sum(v.shape[0] for v in a["values"])
                words += n * inside / (a["t_end"] - a["t_start"])
        return {
            "transfers_per_s": rate(int(done.sum()), t1 - t0),
            "transfer_ms_p95": percentile(lat_ms, 95),
            "audit_words_per_s": rate(words, t1 - t0) if words else None,
        }

    def checks(self) -> dict:
        """Every number compared, with its limit (``bench/reference.py``):
        each audit against the transfers that returned before it began,
        which it must hold, and those that started before it returned,
        which it may; the final state against every acknowledged
        transfer."""
        tr = self.bank.transfers()
        audit_bad = 0
        for a in self.bank.audits():
            maybe = tr["t_start"] <= a["t_end"]
            must = tr["t_end"][maybe] < a["t_start"]
            audit_bad += audit_bad_accounts(
                np.concatenate(a["values"]), self.init, tr["src"][maybe],
                tr["dst"][maybe], must, self.amount)
        return {
            "audit_bad_accounts": {"value": audit_bad, "limit": 0},
            "final_bad_accounts": {
                "value": final_bad_accounts(self.final, self.init,
                                            tr["src"], tr["dst"],
                                            self.amount),
                "limit": 0},
        }
