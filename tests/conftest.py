import os
import sys

# NOTE: deliberately NOT setting xla_force_host_platform_device_count here —
# smoke tests and benches must see the real (single) device; only
# launch/dryrun.py forces 512 (see the assignment's dry-run contract).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    import jax
    return jax.random.PRNGKey(0)


#: the TM kernel wrappers the engine dispatches to on TPU
TM_WRAPPERS = ("snapshot_read", "write_back", "publish_row", "commit_fused",
               "validate_readset", "version_select", "snapshot_select")


@pytest.fixture
def kernel_branch(monkeypatch):
    """Steer the engine onto its TPU branch on CPU: ``ops.on_tpu()``
    reports True and every TM kernel wrapper runs its kernel in the
    Pallas interpreter.  Yields ``ops.COUNTS``, reset at entry."""
    import functools

    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for name in TM_WRAPPERS:
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))
    ops.COUNTS.reset()
    yield ops.COUNTS
    ops.COUNTS.reset()
