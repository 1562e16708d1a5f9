"""MVStore semantics: commits, snapshot reads, modes, controller cycle."""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import MVStoreConfig
from repro.configs.paper_stm import MultiverseParams
from repro.core import modes as M
from repro.core import mvcontroller, mvstore


def params_tree(scale=1.0):
    return {"a": jnp.full((4, 4), scale, jnp.float32),
            "b": {"w": jnp.full((8,), 2 * scale, jnp.float32)}}


def test_mode_q_commit_is_in_place_no_rings():
    cfg = MVStoreConfig(ring_slots=2, mode="Q")
    st = mvstore.mv_init(params_tree(), cfg, versioned="none")
    st2 = mvstore.mv_commit(st, params_tree(2.0), local_mode="Q", cfg=cfg)
    assert int(st2.clock) == 1 and not st2.ring
    view, ok = mvstore.mv_snapshot(st2, read_clock=1)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(view["a"]), 2.0)


def test_mode_q_reader_aborts_when_clock_advances():
    cfg = MVStoreConfig(ring_slots=2, mode="Q")
    st = mvstore.mv_init(params_tree(), cfg, versioned="none")
    st = mvstore.mv_commit(st, params_tree(2.0), local_mode="Q", cfg=cfg)
    # reader began before the commit (read clock 0) -> must abort
    _, ok = mvstore.mv_snapshot(st, read_clock=0)
    assert not bool(ok)


def test_mode_u_commit_keeps_old_version_readable():
    cfg = MVStoreConfig(ring_slots=2, mode="U")
    st = mvstore.mv_init(params_tree(1.0), cfg, versioned="all")
    st = mvstore.mv_commit(st, params_tree(2.0), local_mode="U", cfg=cfg)
    st = mvstore.mv_commit(st, params_tree(3.0), local_mode="U", cfg=cfg)
    # read at clock 1 -> the 2.0 version (ring holds last 2 versions)
    view, ok = mvstore.mv_snapshot(st, read_clock=1)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(view["a"]), 2.0)
    view, ok = mvstore.mv_snapshot(st, read_clock=2)
    np.testing.assert_array_equal(np.asarray(view["a"]), 3.0)


def test_ring_overflow_aborts_reader():
    cfg = MVStoreConfig(ring_slots=2, mode="U")
    st = mvstore.mv_init(params_tree(), cfg, versioned="all")
    for i in range(4):
        st = mvstore.mv_commit(st, params_tree(float(i)), local_mode="U",
                               cfg=cfg)
    # clock=4; ring holds versions at clocks 3 and 4; reading at 1 fails
    _, ok = mvstore.mv_snapshot(st, read_clock=1)
    assert not bool(ok)
    _, ok = mvstore.mv_snapshot(st, read_clock=3)
    assert bool(ok)


def test_mode_u_commit_requires_versioned_blocks():
    cfg = MVStoreConfig(ring_slots=2, mode="U")
    st = mvstore.mv_init(params_tree(), cfg, versioned="none")
    with pytest.raises(ValueError):
        mvstore.mv_commit(st, params_tree(2.0), local_mode="U", cfg=cfg)


def test_partial_versioning_mode_q():
    """Word-granularity insight at block level: only requested blocks get
    rings; snapshot mixes ring reads and validated live reads."""
    cfg = MVStoreConfig(ring_slots=2, mode="Q")
    st = mvstore.mv_init(params_tree(), cfg, versioned="none")
    paths = [p for p in mvstore.block_paths(st.live) if "a" in p]
    st = mvstore.version_blocks(st, set(paths), cfg)
    assert mvstore.versioned_paths(st) == frozenset(paths)
    st = mvstore.mv_commit(st, params_tree(5.0), local_mode="Q", cfg=cfg)
    # reading at clock 0: 'a' resolves via ring (old version), but the
    # unversioned 'b' fails validation -> reader aborts (paper Mode Q)
    _, ok = mvstore.mv_snapshot(st, read_clock=0)
    assert not bool(ok)
    view, ok = mvstore.mv_snapshot(st, read_clock=1)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(view["a"]), 5.0)


def test_unversion_blocks_drops_rings():
    cfg = MVStoreConfig(ring_slots=2, mode="U")
    st = mvstore.mv_init(params_tree(), cfg, versioned="all")
    assert mvstore.ring_bytes(st) > 0
    st = mvstore.unversion_blocks(st, set(mvstore.block_paths(st.live)))
    assert mvstore.ring_bytes(st) == 0


def test_snapshot_pallas_path_matches_xla(kernel_branch):
    cfg = MVStoreConfig(ring_slots=4, mode="U")
    st = mvstore.mv_init(params_tree(), cfg, versioned="all")
    for i in range(3):
        st = mvstore.mv_commit(st, params_tree(float(i)), local_mode="U",
                               cfg=cfg)
    v1, ok1 = mvstore.mv_snapshot(st, read_clock=2, impl="xla")
    v2, ok2 = mvstore.mv_snapshot(st, read_clock=2, impl="pallas")
    assert bool(ok1) == bool(ok2)
    for a, b in zip(jax.tree.leaves(v1), jax.tree.leaves(v2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert kernel_branch.entries["snapshot_select"] > 0


def test_controller_full_mode_cycle():
    """Reader aborts CAS the mode to QtoU; the controller walks
    QtoU->U->UtoQ->Q as participants catch up and stickies clear.

    Driven SYNCHRONOUSLY (``start_bg=False`` + ``step_once``): each
    transition depends only on announcement state, so the test asserts
    the walk deterministically instead of sleeping until a background
    poller observes it."""
    params = MultiverseParams(k1=1, k2=1, k3=1, s=1)
    ctl = mvcontroller.MVController(params=params,
                                    mvcfg=MVStoreConfig(ring_slots=2),
                                    start_bg=False)
    cfg = ctl.mvcfg
    st = mvstore.mv_init(params_tree(), cfg, versioned="none")
    reader = ctl.reader()
    st = ctl.trainer_tick(st)

    # reader aborts repeatedly -> versioned -> CAS to QtoU
    for _ in range(4):
        reader.begin(int(st.clock))
        st = mvstore.mv_commit(st, params_tree(2.0),
                               local_mode=ctl.current_local_mode(),
                               cfg=cfg)
        st = ctl.trainer_tick(st)
        _, ok = mvstore.mv_snapshot(st, read_clock=int(st.clock) - 1)
        reader.on_abort(2)
    assert ctl.mode != M.MODE_Q

    # trainer keeps ticking; controller must reach Mode U
    for _ in range(20):
        if ctl.mode == M.MODE_U:
            break
        st = ctl.trainer_tick(st)
        st = mvstore.mv_commit(st, params_tree(3.0),
                               local_mode=ctl.current_local_mode(),
                               cfg=cfg)
        reader.begin(int(st.clock))
        ctl.step_once()
    assert ctl.mode == M.MODE_U
    assert len(st.ring) == len(mvstore.block_paths(st.live))

    # reader commits small txns -> sticky clears -> back to Q eventually
    for _ in range(20):
        if ctl.mode == M.MODE_Q:
            break
        reader.begin(int(st.clock))
        view, ok = mvstore.mv_snapshot(st, read_clock=int(st.clock),
                                       assume_versioned=True)
        reader.on_commit(1, int(st.clock))
        st = ctl.trainer_tick(st)
        ctl.step_once()
    assert ctl.mode == M.MODE_Q
    ctl.stop()


def test_controller_stale_unversioning():
    cfg = MVStoreConfig(ring_slots=2)
    st = mvstore.mv_init(params_tree(), cfg, versioned="all")
    for i in range(3):
        st = mvstore.mv_commit(st, params_tree(float(i)), local_mode="U",
                               cfg=cfg)
    drop = mvcontroller.apply_stale_unversioning(
        st, {"__stale_older_than:0.5"})
    # newest ring ts == clock -> nothing stale
    assert drop == frozenset()
    # pretend the clock raced ahead
    st = st._replace(clock=jnp.asarray(100, jnp.int32))
    drop = mvcontroller.apply_stale_unversioning(
        st, {"__stale_older_than:50"})
    assert drop == frozenset(st.ring)
