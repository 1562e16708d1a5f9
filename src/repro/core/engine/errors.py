"""Transaction control-flow exceptions (canonical home).

Historically these lived in ``repro.core.stm``; that module re-exports
them so old imports keep working, but the engine layer — and anything
below ``repro.api`` — should import from here.
"""
from __future__ import annotations


class AbortTx(Exception):
    """Transaction abort (longjmp back to beginTxn).

    ``reason`` names the cause where the backend classifies it
    (``repro.api.mvhandle.ABORT_REASONS``), else None."""

    def __init__(self, reason=None):
        super().__init__(*(() if reason is None else (reason,)))
        self.reason = reason


class MaxRetriesExceeded(Exception):
    """A transaction hit the retry cap (baselines quit here; paper SS5)."""
