"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 2, 2, 32),      # MHA
    (2, 256, 4, 2, 64),      # GQA 2:1
    (1, 512, 8, 1, 64),      # MQA
    (2, 128, 4, 4, 128),     # MXU-aligned head dim
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                            interpret=True)
    G = H // KV
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    r = ref.flash_attention_ref(qr, kr, vr, causal=causal)
    r = r.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_matches_blockwise_xla():
    """The XLA blockwise lowering (dry-run path) and the Pallas kernel
    implement the same schedule: they must agree."""
    from repro.models.attention import blockwise_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, KV, D = 2, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    o2 = blockwise_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)
    # and the unrolled probe variant is numerically identical in structure
    o3 = blockwise_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             unroll=True)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o3),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 8, 4, 16),
    (2, 128, 4, 16, 8, 32),
    (1, 256, 2, 32, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    xh = (jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.5
          ).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    B_ = (jax.random.normal(ks[3], (B, S, N), jnp.float32) * 0.5
          ).astype(dtype)
    C_ = (jax.random.normal(ks[4], (B, S, N), jnp.float32) * 0.5
          ).astype(dtype)
    y, _ = ops.ssd_scan(xh, dt, A, B_, C_, chunk=chunk, interpret=True)
    yr, _ = ref.ssd_scan_ref(xh, dt, A, B_, C_)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)


def test_ssd_xla_chunked_matches_sequential_ref():
    from repro.models.mamba import ssd_chunk_scan
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, P, N = 2, 128, 4, 16, 8
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, N)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, N)) * 0.5
    for unroll in (False, True):
        y, st = ssd_chunk_scan(xh, dt, A, B_, C_, chunk=32, unroll=unroll)
        yr, str_ = ref.ssd_scan_ref(xh, dt, A, B_, C_)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("R,shape", [(2, (64,)), (4, (8, 16)),
                                     (8, (4, 4, 8)), (3, (100,))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_snapshot_select_sweep(R, shape, dtype):
    key = jax.random.PRNGKey(4)
    if dtype == jnp.int32:
        ring = jax.random.randint(key, (R,) + shape, 0, 100, jnp.int32)
    else:
        ring = jax.random.normal(key, (R,) + shape, jnp.float32
                                 ).astype(dtype)
    ts = jnp.asarray(np.random.RandomState(0).permutation(R) * 3 - 1,
                     jnp.int32)
    for clock in (-1, 0, 2, 5, 100):
        val, ok = ops.snapshot_select(ring, ts, jnp.int32(clock),
                                      interpret=True)
        vr, okr = ref.snapshot_select_ref(
            ring.reshape(R, -1), ts, clock)
        assert bool(ok) == bool(okr)
        if bool(okr):
            np.testing.assert_array_equal(
                np.asarray(val).ravel(), np.asarray(vr))


@pytest.mark.parametrize("shape", [(64,), (24, 16), (3, 5, 8)])
@pytest.mark.parametrize("with_ring", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_adamw_sweep(shape, with_ring, dtype):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    p = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    g = jax.random.normal(ks[1], shape, jnp.float32)
    m = jax.random.normal(ks[2], shape, jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], shape, jnp.float32)) * 0.01
    ring = jnp.zeros((3,) + shape, dtype) if with_ring else None
    kw = dict(lr=jnp.float32(3e-3), scale=jnp.float32(0.7), b1=0.9,
              b2=0.95, eps=1e-8, wd=0.1)
    p2, m2, v2, r2 = ops.fused_adamw(p, g, m, v, ring, 2,
                                     count=jnp.int32(3), interpret=True,
                                     **kw)
    cnt = jnp.float32(3)
    pr, mr, vr2, rr = ref.fused_adamw_ref(
        p.reshape(-1), g.reshape(-1), m.reshape(-1), v.reshape(-1),
        ring.reshape(3, -1) if with_ring else None, 2,
        b1c=1 - 0.9 ** cnt, b2c=1 - 0.95 ** cnt, **kw)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(p2.reshape(-1), np.float32),
                               np.asarray(pr, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(m2.reshape(-1)), np.asarray(mr),
                               rtol=1e-5, atol=1e-5)
    if with_ring:
        np.testing.assert_allclose(
            np.asarray(r2.reshape(3, -1), np.float32),
            np.asarray(rr, np.float32), rtol=tol, atol=tol)
        # untouched slots stay zero
        assert float(jnp.abs(r2[0]).sum()) == 0.0


# ---------------------------------------------------------------------------
# bulk read-set validation kernel vs the scalar Python validator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", [0, 1, 2])          # V_LT / V_LE / V_EQ
@pytest.mark.parametrize("n", [1, 7, 512, 1000])
def test_validate_readset_kernel_matches_scalar(mode, n):
    """The Pallas kernel, the numpy twin and the word-at-a-time scalar
    validator must agree on every (lock word, read entry) combination."""
    from repro.core.engine import validation as V
    from repro.core.engine.arrayheap import ArrayLockTable
    from repro.core.locks import LockState

    rng = np.random.default_rng(17 * mode + n)
    lt = ArrayLockTable(9)
    for idx in rng.integers(0, 1 << 9, 150):
        lt.store(int(idx), LockState(
            bool(rng.integers(2)), int(rng.integers(0, 30)),
            int(rng.integers(-2, 4)), bool(rng.integers(2))))
    read_set = [(int(i), int(rng.integers(0, 30)))
                for i in rng.integers(0, 1 << 9, n)]
    idxs = np.array([e[0] for e in read_set], np.int64)
    seen = np.array([e[1] for e in read_set], np.int64)
    ver, own, meta = lt.gather(idxs)
    for r_clock, tid in [(0, 0), (15, 1), (29, -1)]:
        scalar = V.revalidate_scalar(lt, read_set, r_clock, tid, mode)
        via_np = V.np_validate(ver, own, meta, seen, r_clock, tid, mode)
        via_kernel = ops.validate_readset(ver, own, meta, seen, r_clock,
                                          tid, mode, interpret=True)
        assert scalar == via_np == via_kernel, (mode, n, r_clock, tid)


def test_validate_readset_kernel_elementwise_mask():
    """Per-element mask parity (not just the AND): each lane of the kernel
    must equal the scalar predicate for its lock word."""
    from repro.core.engine import validation as V
    from repro.kernels import validate as vk
    from repro.core.locks import LockState

    states = []
    for locked in (False, True):
        for tid in (-2, 0, 1):
            for flag in (False, True):
                for version in (0, 3, 7):
                    states.append(LockState(locked, version, tid, flag))
    ver = jnp.asarray([s.version for s in states], jnp.int32)
    own = jnp.asarray([s.tid for s in states], jnp.int32)
    meta = jnp.asarray([int(s.locked) | (int(s.flag) << 1)
                        for s in states], jnp.int32)
    seen = jnp.asarray([s.version if i % 2 == 0 else s.version + 1
                        for i, s in enumerate(states)], jnp.int32)
    # the kernel's contract: [N / 128, 128] int32, one 1024-entry tile
    pad = (-len(states)) % 1024
    pd = vk.PAD

    def prep(x, fill):
        return jnp.pad(x, (0, pad), constant_values=fill).reshape(
            -1, vk.LANES)

    for mode in (0, 1, 2):
        mask = vk.validate_readset_flat(
            prep(ver, pd["ver"]), prep(own, pd["own"]),
            prep(meta, pd["meta"]), prep(seen, pd["seen"]),
            r_clock=5, tid=0, mode=mode, tile=1024,
            interpret=True).reshape(-1)
        for i, s in enumerate(states):
            want = V.check_entry(s, int(seen[i]), 5, 0, mode)
            assert bool(mask[i]) == want, (mode, i, s)
        assert bool(jnp.all(mask[len(states):] == 1))   # padding all-valid


def test_validate_readset_survives_64bit_clock():
    """Lock versions exceed int32 in long runs (the packed word gives the
    version 46 bits); ops.validate_readset rebases to r_clock before the
    int32 kernel, so it must agree with the int64 numpy twin out there."""
    from repro.core.engine import validation as V

    big = (1 << 31) + 12345
    ver = np.asarray([big, big + 1, big - 1, big - 3], np.int64)
    own = np.full(4, -1, np.int32)
    meta = np.zeros(4, np.int32)
    seen = ver.copy()
    for mode, r_clock in [(0, big), (0, big + 2), (1, big), (2, big + 2)]:
        want = V.np_validate(ver, own, meta, seen, r_clock, 0, mode)
        got = ops.validate_readset(ver, own, meta, seen, r_clock, 0, mode,
                                   interpret=True)
        assert got == want, (mode, r_clock, got, want)
    # stale entry at a 64-bit clock: version == r_clock fails V_LT
    assert not ops.validate_readset(
        np.asarray([big], np.int64), own[:1], meta[:1],
        np.asarray([big], np.int64), big, 0, 0, interpret=True)


# ---------------------------------------------------------------------------
# TM wrappers vs their numpy twins at the chip tiling (interpret mode):
# ragged batches below one (8, 128) tile, just past it and over several
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 1025, 3000])
def test_tm_wrappers_match_twins_at_chip_tiles(n):
    from repro.core.vlt import np_version_select
    from repro.kernels import commit_fused as cf
    from repro.kernels.scatter_write import np_write_back

    rng = np.random.default_rng(n)
    h = 5000 + n                       # not a whole number of rows
    heap = rng.integers(-1000, 1000, h).astype(np.int64)
    a = rng.integers(0, h, n)
    np.testing.assert_array_equal(
        np.asarray(ops.snapshot_read(heap, a, interpret=True)), heap[a])
    au = rng.choice(h, n, replace=False)
    v = rng.integers(-50, 50, n)
    want = np_write_back(heap, au, v)
    np.testing.assert_array_equal(
        ops.write_back(heap, au, v, interpret=True), want)
    np.testing.assert_array_equal(np.asarray(ops.publish_row(
        jnp.asarray(heap, jnp.int32), au, v, interpret=True)), want)
    ts = rng.integers(0, 20, (n, 4))
    data = rng.integers(-99, 99, (n, 4))
    for clock in (0, 7, 21):
        gv, go = ops.version_select(ts, data, clock, interpret=True)
        wv, wo = np_version_select(ts, data, clock)
        np.testing.assert_array_equal(go, wo)
        np.testing.assert_array_equal(gv[wo], wv[wo])
    # one member per write, every other member's single lock held by a
    # foreign owner: the fused verdict and scatter against the twin
    seg = np.arange(n)
    from repro.core.engine.arrayheap import pack_lock
    from repro.core.locks import LockState
    l_words = np.array([pack_lock(LockState(t % 2 == 1, 3, n + 7, False))
                        for t in range(n)], np.int64)
    z = np.zeros((0,), np.int64)
    got = ops.commit_fused(jnp.asarray(heap, jnp.int32), au, v, seg,
                           l_words, seg, z, z, z, seg, np.full(n, 9), 10,
                           n, mode=cf.MODE_LE, interpret=True)
    ref_ = ops.commit_fused(heap, au, v, seg, l_words, seg, z, z, z, seg,
                            np.full(n, 9), 10, n, mode=cf.MODE_LE)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref_[0]))
    np.testing.assert_array_equal(got[1], ref_[1])
    np.testing.assert_array_equal(got[2], ref_[2])
    assert got[1].tolist() == [t % 2 == 0 for t in range(n)]
