"""Every TM kernel compiles for a TPU v5e chip at ``chip_smoke.py``'s shapes.

The chip's compiler is installed with JAX and compiles for a described,
unattached v5e: these tests lower the very jitted programs the ``ops``
wrappers launch on the chip (``interpret=False``) and compile them for
one chip of a ``v5e:2x2`` topology.  A kernel the chip would refuse —
an unaligned block, an unsupported gather, too much VMEM — fails here,
without a chip.  Nothing runs, so results and times are out of scope.

Every TM kernel has a chip kernel; none is routed to a plain XLA
program instead.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HEAP = 1 << 22          # chip_smoke's ArrayHeap (words)
CHUNK = 1 << 16         # its read_bulk chunk
WRITES = 4096           # its kernels-phase write batch
ROWS = 4096             # its validate / version_select batch
RING = 1 << 20          # its snapshot_select ring row
BLOCK = 1 << 26         # a whole-block read_bulk of a 2^26-word store block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _programs(one_chip):
    s = lambda *shape: _spec(one_chip, shape)     # noqa: E731
    wt = ops.tile_for(WRITES, 1024)
    return {
        "gather_read": (
            lambda h, a: ops._gather(h, a, tile=1024, interpret=False),
            [s(HEAP), s(CHUNK)]),
        # a heap length that is not a whole number of 128-word rows
        "gather_read_ragged_heap": (
            lambda h, a: ops._gather(h, a, tile=1024, interpret=False),
            [s(HEAP + 3), s(CHUNK)]),
        # 65,536 grid steps: the per-step path flags reach SMEM a block
        # at a time, so the batch length is bounded by HBM alone
        "gather_read_whole_block": (
            lambda h, a: ops._gather(h, a, tile=1024, interpret=False),
            [s(BLOCK), s(BLOCK)]),
        "scatter_write": (
            lambda r, a, v: ops._scatter(r, a, v, tile=wt,
                                         interpret=False),
            [s(HEAP), s(WRITES), s(WRITES)]),
        "validate": (
            lambda v, o, m, n, p: ops._validate(v, o, m, n, p, tile=1024,
                                                interpret=False),
            [s(ROWS)] * 4 + [s(3)]),
        "version_select": (
            lambda t, d: ops._version_select(t, d, tile=1024,
                                             interpret=False),
            [s(ROWS, 4), s(ROWS, 4)]),
        "snapshot_select": (
            lambda r, t, c: ops._ss.snapshot_select_flat(r, t, c),
            [s(2, RING), s(2), s()]),
        "snapshot_select_bf16": (
            lambda r, t, c: ops._ss.snapshot_select_flat(r, t, c),
            [_spec(one_chip, (2, RING), jnp.bfloat16), s(2), s()]),
        "commit_fused": (
            lambda *a: ops._commit_fused_jit(*a, mode=1, tile=wt,
                                             interpret=False),
            [s(HEAP), s(WRITES), s(WRITES), s(WRITES)]
            + [s(64)] * 4 + [s(256)] * 5 + [s(33), s(33), s(1)]),
    }


KERNELS = ("gather_read", "gather_read_ragged_heap",
           "gather_read_whole_block", "scatter_write",
           "validate", "version_select", "snapshot_select",
           "snapshot_select_bf16", "commit_fused")


@pytest.mark.parametrize("name", KERNELS)
def test_tm_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, args = _programs(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    # the chip kernel itself is in the program, not an XLA stand-in
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the heap stays in HBM: no temp buffer the size of the heap
    assert mem.temp_size_in_bytes < HEAP, mem
