"""Plain reference of the bank's semantics, independent of the system.

The bank holds ``accounts`` int balances.  A transfer moves a fixed
``amount`` from one account to another; an audit reads every balance.
Nothing here imports the system under test or takes anything it made:
the reference starts from the balances the seed gives and applies the
operations the harness recorded.

Two comparisons decide ``correct``:

* ``final_bad_accounts``: after the window, every acknowledged transfer
  must be in the state exactly once and nothing else may be.  Transfers
  commute, so the expected state is the initial one plus the net of all
  acknowledged transfers, whatever order they committed in.
* ``audit_bad_accounts``: an audit must read one consistent state that
  respects real time: the initial balances plus every transfer that
  returned before the audit began, plus a set of whole transfers among
  those that started before the audit returned.  With the first kind
  taken out, per account the audit's remaining change must be a whole
  number of the other transfers the account could have taken part in;
  a transfer that is the only candidate on one of its accounts is in
  or out by that account's reading, and its other account must agree
  (a transfer seen half is a torn read).  A stale snapshot, an answer
  served from a cache or an old version, misses a transfer of the
  first kind and fails.

``ReferenceBank`` is the same bank as a plain in-memory object, run in
the system's place by the control and by the tests.
"""
from __future__ import annotations

import threading

import numpy as np

__all__ = ["initial_balances", "expected_final", "final_bad_accounts",
           "audit_bad_accounts", "ReferenceBank", "RetriesExhausted"]


class RetriesExhausted(Exception):
    """An operation ran out of attempts (counted as failed, not wrong)."""


def initial_balances(seed: int, accounts: int, low: int, high: int
                     ) -> np.ndarray:
    """The seeded opening balances, int64[accounts] in ``[low, high)``."""
    rng = np.random.default_rng([abs(int(seed)), 0x62616E6B])
    return rng.integers(low, high, accounts, dtype=np.int64)


def expected_final(init: np.ndarray, src: np.ndarray, dst: np.ndarray,
                   amount: int) -> np.ndarray:
    """``init`` with every listed transfer applied once."""
    out = init.astype(np.int64, copy=True)
    np.subtract.at(out, np.asarray(src, np.int64), amount)
    np.add.at(out, np.asarray(dst, np.int64), amount)
    return out


def final_bad_accounts(got: np.ndarray, init: np.ndarray, src, dst,
                       amount: int) -> int:
    """Accounts whose final balance differs from the reference."""
    want = expected_final(init, src, dst, amount)
    got = np.asarray(got, np.int64)
    if got.shape != want.shape:
        return int(want.shape[0])
    return int(np.count_nonzero(got != want))


def audit_bad_accounts(values: np.ndarray, init: np.ndarray, src, dst,
                       must, amount: int) -> int:
    """Accounts at which an audit's reading cannot be a consistent state
    that respects real time.

    ``values`` is what the audit read; ``src``/``dst`` are the
    acknowledged transfers that started before the audit returned, and
    ``must`` marks those among them that returned before the audit
    began, which its state has to hold.  Returns 0 for a sound reading.
    """
    values = np.asarray(values, np.int64)
    if values.shape != init.shape:
        return int(init.shape[0])
    n = init.shape[0]
    must = np.asarray(must, bool)
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    delta = values - expected_final(init, src[must], dst[must], amount)
    src, dst = src[~must], dst[~must]
    out_cnt = np.bincount(src, minlength=n)
    in_cnt = np.bincount(dst, minlength=n)
    moves = delta // amount
    bad = (delta % amount != 0) | (moves < -out_cnt) | (moves > in_cnt)
    if src.size:
        touch = out_cnt + in_cnt
        s_only = touch[src] == 1
        d_only = touch[dst] == 1
        s_in = moves[src] == -1
        d_in = moves[dst] == 1
        torn = s_only & d_only & (s_in != d_in)
        bad[src[torn]] = True
        bad[dst[torn]] = True
    return int(np.count_nonzero(bad))


class _RefTxn:
    __slots__ = ("_bank", "writes")

    def __init__(self, bank: "ReferenceBank"):
        self._bank = bank
        self.writes = {}

    def read(self, addr: int) -> int:
        if addr in self.writes:
            return self.writes[addr]
        return int(self._bank.balances[addr])

    def read_bulk(self, addrs) -> np.ndarray:
        a = np.asarray(addrs, np.int64) if not isinstance(addrs, range) \
            else np.arange(addrs.start, addrs.stop, dtype=np.int64)
        vals = self._bank.balances[a]
        for i, x in enumerate(a.tolist() if self.writes else ()):
            if x in self.writes:
                vals[i] = self.writes[x]
        return vals

    def write(self, addr: int, value: int) -> None:
        self.writes[int(addr)] = int(value)


class ReferenceBank:
    """The bank as a plain array under one lock: every operation runs
    whole under the lock, so operations are trivially serializable.

    ``write_behind=True`` is the control: a transfer is acknowledged
    before it is applied, and is applied only when the same thread
    starts its next operation.  It breaks the guarantee that an
    acknowledged transfer is in the state, and nothing else.
    """

    #: the address of account 0
    base = 0

    def __init__(self, balances: np.ndarray, *, write_behind: bool = False):
        self.balances = np.array(balances, np.int64, copy=True)
        self.write_behind = write_behind
        self._lock = threading.Lock()
        self._pending = {}

    def run(self, fn, tid: int = 0, max_retries: int = 0):
        with self._lock:
            for addr, v in self._pending.pop(tid, {}).items():
                self.balances[addr] = v
            tx = _RefTxn(self)
            result = fn(tx)
            if self.write_behind and tx.writes:
                self._pending[tid] = tx.writes
            else:
                for addr, v in tx.writes.items():
                    self.balances[addr] = v
            return result

    def read_all(self) -> np.ndarray:
        with self._lock:
            return self.balances.copy()

    def stop(self) -> None:
        pass
