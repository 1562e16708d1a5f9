"""``chip_smoke.py`` and the platform dispatch, on CPU at tiny sizes.

The phases are plain functions: here they run through the engine's
TPU branch (the ``kernel_branch`` fixture steers ``ops.on_tpu`` and
runs every kernel in the Pallas interpreter), or, for the serve and
four-shard phases, through the CPU paths.  ``main()`` itself insists
on a TPU, which these tests check from the outside.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), **kw)
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def test_platform_decision_picks_twins_on_cpu():
    """On CPU no engine site enters a kernel: bulk reads, bulk
    validation, versioned selects and group commits take the twins."""
    from repro.api import make_tm, run
    from repro.configs.paper_stm import MultiverseParams
    from repro.core.engine.groupcommit import CommitBatcher
    from repro.kernels import ops

    assert ops.on_tpu() is False
    ops.COUNTS.reset()
    tm = make_tm("multiverse", 2, params=MultiverseParams(
        lock_table_bits=8), array_heap=True)
    base = tm.alloc(600, 3)

    def wide(tx):
        vals = np.asarray(tx.read_bulk(range(base, base + 600)))
        tx.write(base, int(vals.sum()))
    run(tm, wide, tid=0)
    assert tm.raw.heap[base] == 1800
    tm.stop()
    g = make_tm("tl2", 2, array_heap=True)
    gb = g.alloc(8)
    b = CommitBatcher(g.raw)
    for t in range(2):
        tx = g.raw.begin(t)
        tx.write(gb + t, 5 + t)
        b.add(tx)
    assert b.commit_all() == [True, True]
    g.stop()
    assert not ops.COUNTS.entries and not ops.COUNTS.twin_routes


def test_int32_guard_routes_wide_heap_to_twin(kernel_branch):
    """A word beyond int32 in the heap: the device read must not
    truncate it.  ``ArrayHeap.jnp`` refuses, and the kernel branch of
    ``read_bulk`` routes that heap to the numpy twin (counted)."""
    from repro.api import make_tm, run
    from repro.configs.paper_stm import MultiverseParams

    big = (1 << 40) + 7
    tm = make_tm("tl2", 1, params=MultiverseParams(lock_table_bits=8),
                 array_heap=True)
    base = tm.alloc(300, 1)
    run(tm, lambda tx: tx.write(base + 5, big), tid=0)
    heap = tm.raw.heap
    assert not heap.fits_int32
    with pytest.raises(OverflowError):
        heap.jnp()
    from repro.kernels import ops
    with pytest.raises(OverflowError):
        ops.snapshot_read(heap.gather(np.arange(len(heap))), np.arange(4))
    got = run(tm, lambda tx: tx.read_bulk(range(base, base + 300)), tid=0)
    assert int(got[5]) == big and int(np.asarray(got).sum()) == big + 299
    assert kernel_branch.twin_routes["gather_read"] >= 1
    assert kernel_branch.entries["gather_read"] == 0
    tm.stop()


def test_kernel_branch_reads_narrow_heap_on_device(kernel_branch):
    from repro.api import make_tm, run

    tm = make_tm("tl2", 1, array_heap=True)
    base = tm.alloc(300, 2)
    got = run(tm, lambda tx: tx.read_bulk(range(base, base + 300)), tid=0)
    assert int(np.asarray(got).sum()) == 600
    assert kernel_branch.entries["gather_read"] == 1
    assert not kernel_branch.twin_routes
    tm.stop()


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------


def test_kernels_phase_tiny(kernel_branch):
    out = chip_smoke.kernels_phase(heap_words=1 << 12, batch=1 << 10,
                                   writes=256, rows=300,
                                   ring_words=1 << 11)
    assert set(out) == {"gather_read", "scatter_write", "validate",
                        "version_select", "snapshot_select",
                        "commit_fused"}
    for name in out:
        assert kernel_branch.entries[name] >= 2, name


def test_tm_phase_tiny(kernel_branch):
    out = chip_smoke.tm_phase(heap_words=1 << 12, region=1 << 10,
                              chunk=1 << 8, seconds=1.5, group_rounds=3,
                              group_size=8, mv_words=1 << 10,
                              mv_commits=20)
    assert out["scan"]["violations"] == 0 and out["scan"]["scans"] >= 1
    assert out["groups"]["groups"] >= 1
    assert out["mvstore"]["commits"] == 20
    assert out["int64_twin_routes"] == {}
    for site in ("gather_read", "validate", "version_select",
                 "commit_fused"):
        assert out["site_entries"][site] >= 1, site
        assert out["h2d_bytes"][site] > 0, site
    # the word heap is uploaded for every kernel gather, and gathered
    # words come back to the host
    assert out["h2d_bytes"]["heap_upload"] > 0
    assert out["d2h_bytes"]["gather_read"] > 0
    scan = out["scan"]
    assert scan["bulk_batch_words"] > 0
    assert min(scan["version_gather_hits"], scan["bulk_scalar_words"]) >= 0


def test_serve_phase_smoke_width():
    from repro.configs import smoke_config
    out = chip_smoke.serve_phase(smoke_config("qwen2.5-3b"), slots=2,
                                 requests=4, prompt_len=16, gen=4)
    assert out["completed"] == 4 and out["tokens"] == 16
    assert out["commits"] >= 1 and out["aborts"] >= 1


def test_four_chips_phase_on_four_host_devices():
    """The four-shard parity run in a process with four CPU devices:
    each shard's buffers on its own device, final heap equal to one
    store's."""
    code = ("import chip_smoke, json; print(json.dumps("
            "chip_smoke.four_chips_phase(words=4096, n_ops=30)))")
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["shards"] == 4 and len(set(got["devices"])) == 4
    assert got["cross_commits"] >= 1


# ---------------------------------------------------------------------------
# main() refuses to run without a chip
# ---------------------------------------------------------------------------


def test_main_refuses_cpu():
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=300, env=_env())
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and "phase" not in p.stdout


def test_main_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert p.returncode != 0 and '"ok"' not in p.stdout
