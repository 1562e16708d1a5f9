"""Sharding + dry-run machinery on a small in-process mesh.

The production 512-device dry-run runs via launch/dryrun.py in its own
process (XLA device count is locked at first init); here we verify the
same code paths on an 8-device mesh spawned in a subprocess, plus the
mesh/rules/roofline utilities in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import roofline
from repro.launch.sharding import Rules, default_rules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_rules_mapping():
    import jax
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    r = default_rules(mesh)
    assert r.get("batch") == ("data",)
    assert r.get("tp") is None
    r2 = r.with_(batch=None)
    assert r2.get("batch") is None
    assert r.spec(("batch", None, "tp")) == jax.sharding.PartitionSpec(
        ("data",), None, None)


def test_collective_bytes_parser():
    hlo = textwrap.dedent("""
      ENTRY %main {
        %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}
        %ag = bf16[64,64]{1,0} all-gather(%y), replica_groups=[8,2]<=[16]
        %cp = f32[32]{0} collective-permute(%z)
        %dot = f32[8,8]{1,0} dot(%a, %b)
      }
    """)
    out = roofline.collective_bytes(hlo, default_group=4)
    assert out["ops"]["all-reduce"]["count"] == 1
    ar_bytes = 128 * 256 * 4
    assert out["ops"]["all-reduce"]["result_bytes"] == ar_bytes
    assert out["ops"]["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * ar_bytes * 3 / 4)
    assert out["ops"]["all-gather"]["result_bytes"] == 64 * 64 * 2
    assert out["ops"]["collective-permute"]["wire_bytes"] == 32 * 4
    assert len(out["top"]) == 3


def test_roofline_terms_identifies_dominant():
    from repro.configs import get_config, get_shape
    cfg = get_config("qwen2.5-3b")
    shape = get_shape("train_4k")
    terms = roofline.roofline_terms(
        cfg, shape, cost={"flops": 1e14, "bytes accessed": 1e11},
        collectives={"total_wire_bytes": 1e9}, n_chips=256)
    assert terms["dominant"] == "compute"
    assert terms["t_compute_s"] == pytest.approx(1e14 / 197e12)
    assert 0 < terms["roofline_fraction"] <= 1.5
    # peaks are keyed by device kind: an unknown device is an error
    with pytest.raises(KeyError):
        roofline.roofline_terms(
            cfg, shape, cost={"flops": 1.0}, collectives={}, n_chips=1,
            device_kind="cpu")


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """Full lower+compile of train/decode steps on an 8-device host mesh —
    the same compile_once path the 512-device dry-run uses."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config, MVStoreConfig, ParallelConfig
        from repro.configs.base import ShapeConfig
        from repro.launch.dryrun import compile_once, cell_rules
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("qwen2.5-3b")
        out = {}
        for kind, mv in (("train", "Q"), ("train", "U"), ("decode", "U")):
            shape = ShapeConfig("t", 64, 8, kind)
            pcfg = ParallelConfig(microbatches=2 if kind == "train" else 1,
                                  remat="block" if kind == "train" else "none",
                                  attn_block_q=32, attn_block_k=32)
            rules = cell_rules(mesh, shape, pcfg)
            c, t = compile_once(cfg, shape, mesh, pcfg,
                                MVStoreConfig(enabled=True, mode=mv),
                                adamw.AdamWConfig(), rules)
            ca = c.cost_analysis()
            if isinstance(ca, (list, tuple)):   # jax<=0.4.x: one per device
                ca = ca[0]
            out[f"{kind}_{mv}"] = {"flops": ca.get("flops"),
                                   "mem": c.memory_analysis().temp_size_in_bytes}
        print(json.dumps(out))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["train_Q"]["flops"] > 0
    # Mode-U versioned commit adds ring writes (more bytes, ~same flops)
    assert out["train_U"]["flops"] >= out["train_Q"]["flops"]
    assert out["decode_U"]["flops"] > 0


def test_tpu_bytes_model_edge_materialization():
    """Edges collapse iff BOTH endpoints are fusable; non-fusable ops
    write their results; params read by anyone count."""
    hlo = textwrap.dedent("""
      %fused_computation.1 {
        %p0 = f32[1024]{0} parameter(0)
        %e = f32[1024]{0} exponential(%p0)
        %m = f32[1024]{0} multiply(%e, %e)
      }
      ENTRY %main {
        %a = f32[128,128]{1,0} parameter(0)
        %b = f32[128,128]{1,0} parameter(1)
        %c = f32[1024]{0} parameter(2)
        %d = f32[128,128]{1,0} dot(%a, %b)
        %big = f32[1024]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1
        %e2 = f32[1024]{0} exponential(%big)
        %add = f32[128,128]{1,0} add(%d, %d)
      }
    """)
    out = roofline.tpu_bytes_model(hlo)
    t = 128 * 128 * 4
    v = 1024 * 4
    # dot: reads a+b (2t) + writes d (t); add reads d twice (2t, add is
    # fusable but producer dot is not); fusion reads param c (v);
    # fusion->exponential edge collapses (both fusable, never read by a
    # non-fusable op). e2's own output is dead (no consumer, not ROOT).
    assert out["tpu_bytes"] == 5 * t + v, out
