import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Two compile flavors per cell:

1. FIT compile (the deliverable): the full production config — scanned layer
   groups, gradient-accumulation scan — lowered with explicit shardings on
   the 16x16 or 2x16x16 mesh and compiled.  memory_analysis() proves the
   cell fits; compile success proves the sharding is coherent.

2. ROOFLINE probes (--probes, single-pod): XLA's cost analysis counts
   while-loop bodies ONCE, so the scanned fit artifact undercounts flops /
   bytes / collective traffic.  Probes re-lower small UNROLLED variants
   (1-2 layer periods, 1-2 microbatches, attention-pair / SSD-chunk /
   decode-chunk loops as python loops) on the SAME mesh and shardings, and
   reconstruct exact per-step totals from the linear structure:
     train:    P(g, m) = S(g) + m*F(g);  S, F linear in layer groups g
     prefill:  P(g)    linear in g  (jamba: quadratic-in-seq fit;
                                     mamba2: linear-in-seq scale)
     decode:   P(g)    linear in g
   Every reconstruction input is itself a compiled artifact's cost
   analysis — no hand-computed flops enter the table.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, MVStoreConfig, ParallelConfig,
                           get_config, get_shape)
from repro.configs.base import ShapeConfig
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import default_rules, use_rules
from repro.launch.steps import (cache_specs, make_decode_step,
                                make_prefill_step, make_train_step,
                                train_state_specs)
from repro.models import model_zoo as zoo
from repro.models import transformer as tfm
from repro.optim import adamw


def default_parallel(cfg, shape, mesh, overrides=None) -> ParallelConfig:
    """Per-cell parallelism defaults (the hillclimb overrides these)."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_ways = axes.get("data", 1) * axes.get("pod", 1)
    kw = {}
    if shape.kind == "train":
        tokens_per_chip = shape.global_batch * shape.seq_len // max(
            data_ways, 1)
        # wide residual streams need smaller microbatches to fit v5e HBM
        mb_tokens = 4096 if cfg.d_model >= 8192 else 8192
        kw["microbatches"] = max(1, min(shape.global_batch // data_ways,
                                        tokens_per_chip // mb_tokens))
        kw["remat"] = "block"
        # two-level remat when the per-period residual saves exceed ~4GB
        from repro.models import transformer as _tfm
        periods = cfg.n_layers // (_tfm.layer_period(cfg)
                                   if not cfg.is_encdec else cfg.n_layers)
        if not cfg.is_encdec:
            per_mb_tok = tokens_per_chip // kw["microbatches"]
            save_bytes = periods * per_mb_tok * cfg.d_model * 2
            if save_bytes > 4e9:
                for k in (2, 4, 8):
                    if periods % k == 0 and save_bytes / k <= 4e9:
                        kw["remat"] = f"group:{k}"
                        break
                else:
                    ks = [k for k in (2, 4, 8) if periods % k == 0]
                    if ks:
                        kw["remat"] = f"group:{ks[-1]}"
    else:
        kw["microbatches"] = 1
        kw["remat"] = "none"
    if shape.kind == "decode" and shape.seq_len >= 262144:
        kw["decode_attn_chunk"] = 8192
    if overrides:
        kw.update(overrides)
    return ParallelConfig(**kw)


def cell_rules(mesh, shape, pcfg, global_batch=None, rules_override=None):
    gb = global_batch if global_batch is not None else shape.global_batch
    ways = 1
    for ax in ("data", "pod"):
        if ax in mesh.axis_names:
            ways *= mesh.devices.shape[mesh.axis_names.index(ax)]
    rules = default_rules(mesh, fsdp=pcfg.fsdp,
                          shard_seq=shape.global_batch == 1)
    if gb % ways != 0:
        rules = rules.with_(batch=None)
    if rules_override:
        rules = rules.with_(**{
            k: (tuple(v) if isinstance(v, list) else v)
            for k, v in rules_override.items()})
    return rules


def shardings_of(tree):
    return jax.tree.map(lambda s: s.sharding, tree)


def compile_once(cfg, shape, mesh, pcfg, mvcfg, opt_cfg, rules):
    """Lower + compile one step; return (compiled, timings)."""
    t0 = time.time()
    with use_rules(rules, mesh):
        if shape.kind == "train":
            step = make_train_step(cfg, pcfg, mvcfg, opt_cfg, rules, mesh)
            state = train_state_specs(cfg, mvcfg, rules, mesh, opt_cfg)
            batch = zoo.input_specs(cfg, shape, rules, mesh)
            fn = jax.jit(step,
                         in_shardings=(shardings_of(state),
                                       shardings_of(batch)),
                         out_shardings=(shardings_of(state), None),
                         donate_argnums=(0,))
            lowered = fn.lower(state, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, pcfg, mvcfg, rules, mesh)
            state = train_state_specs(cfg, mvcfg, rules, mesh, opt_cfg).mv
            batch = zoo.input_specs(cfg, shape, rules, mesh)
            clock = jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=NamedSharding(mesh, P()))
            lowered = jax.jit(step).lower(state, batch, clock)
        else:  # decode
            step = make_decode_step(cfg, pcfg, mvcfg, rules, mesh)
            state = train_state_specs(cfg, mvcfg, rules, mesh, opt_cfg).mv
            cache = cache_specs(cfg, shape, rules, mesh)
            inp = zoo.input_specs(cfg, shape, rules, mesh)
            clock = jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=NamedSharding(mesh, P()))
            fn = jax.jit(step, donate_argnums=(1,))
            lowered = fn.lower(state, cache, inp["cache_len"],
                               inp["token"], clock)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    return compiled, {"lower_s": round(t_lower, 1),
                      "compile_s": round(time.time() - t0 - t_lower, 1)}


_NUM_KEYS = ("flops", "bytes", "tpu_bytes", "wire_bytes",
             "coll_result_bytes")


def _metrics(compiled):
    cost = compiled.cost_analysis()
    try:
        text = compiled.as_text()
    except Exception:
        text = ""
    coll = roofline.collective_bytes(text)
    tb = roofline.tpu_bytes_model(text)
    return {
        "flops": float(cost.get("flops") or 0.0),
        "bytes": float(cost.get("bytes accessed") or 0.0),
        "tpu_bytes": float(tb.get("tpu_bytes") or 0.0),
        "wire_bytes": float(coll.get("total_wire_bytes") or 0.0),
        "coll_result_bytes": float(coll.get("total_result_bytes") or 0.0),
        "coll_ops": coll.get("ops", {}),
        "coll_top": coll.get("top", []),
    }


def _probe_cfgs(cfg):
    period = tfm.layer_period(cfg) if not cfg.is_encdec else 1

    def reduced(g):
        kw = {"n_layers": g * period}
        if cfg.is_encdec:
            kw["n_encoder_layers"] = g
        return dataclasses.replace(cfg, **kw)

    return reduced


def run_probes(arch, shape_name, *, mv_mode, overrides,
               rules_override=None):
    """Roofline probes on the single-pod mesh; returns reconstructed
    per-device metrics + the probe ledger."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=False)
    pcfg0 = default_parallel(cfg, shape, mesh, overrides)
    mvcfg = MVStoreConfig(enabled=True, mode=mv_mode)
    opt_cfg = adamw.AdamWConfig()
    reduced = _probe_cfgs(cfg)
    G = (cfg.n_layers // tfm.layer_period(cfg)) if not cfg.is_encdec \
        else cfg.n_layers
    M = pcfg0.microbatches
    ledger = []

    def probe(g, m=1, seq=None):
        cfg_g = reduced(g)
        gb = shape.global_batch
        sq = shape.seq_len
        if shape.kind == "train":
            gb = m * (shape.global_batch // M)
        if seq is not None:
            sq = seq
        shp = ShapeConfig(shape.name, sq, gb, shape.kind)
        pcfg = dataclasses.replace(pcfg0, microbatches=m, probe_unroll=True,
                                   scan_layers=False)
        rules = cell_rules(mesh, shape, pcfg, global_batch=gb,
                           rules_override=rules_override)
        c, t = compile_once(cfg_g, shp, mesh, pcfg, mvcfg, opt_cfg, rules)
        met = _metrics(c)
        ledger.append({"g": g, "m": m, "seq": sq, "batch": gb, **t,
                       **{k: met[k] for k in ("flops", "bytes", "tpu_bytes",
                                              "wire_bytes")}})
        return met

    ssm_prefill = (cfg.family in ("ssm", "hybrid")
                   and shape.kind == "prefill")
    if shape.kind == "train":
        p11, p21 = probe(1, 1), probe(2, 1)
        p12, p22 = probe(1, 2), probe(2, 2)
        F1 = {k: p12[k] - p11[k] for k in _NUM_KEYS}
        F2 = {k: p22[k] - p21[k] for k in _NUM_KEYS}
        S1 = {k: 2 * p11[k] - p12[k] for k in _NUM_KEYS}
        S2 = {k: 2 * p21[k] - p22[k] for k in _NUM_KEYS}
        total = {k: S1[k] + (G - 1) * (S2[k] - S1[k])
                 + M * (F1[k] + (G - 1) * (F2[k] - F1[k]))
                 for k in _NUM_KEYS}
    elif ssm_prefill and cfg.family == "ssm":
        s1 = 4096
        p1, p2 = probe(1, seq=s1), probe(2, seq=s1)
        scale = shape.seq_len / s1
        total = {k: scale * (p1[k] + (G - 1) * (p2[k] - p1[k]))
                 for k in _NUM_KEYS}
    elif ssm_prefill:  # hybrid: quadratic-in-seq fit (attention layers)
        s1, s2, st = 4096, 8192, shape.seq_len

        def fit(pa, pb):
            out = {}
            for k in _NUM_KEYS:
                c2 = (pb[k] - 2 * pa[k]) / (2.0 * s1 * s1)
                b1 = (4 * pa[k] - pb[k]) / (2.0 * s1)
                out[k] = b1 * st + c2 * st * st
            return out

        q1 = fit(probe(1, seq=s1), probe(1, seq=s2))
        q2 = fit(probe(2, seq=s1), probe(2, seq=s2))
        total = {k: q1[k] + (G - 1) * (q2[k] - q1[k]) for k in _NUM_KEYS}
    else:
        p1, p2 = probe(1), probe(2)
        total = {k: p1[k] + (G - 1) * (p2[k] - p1[k]) for k in _NUM_KEYS}
    total = {k: max(v, 0.0) for k, v in total.items()}
    return total, ledger


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               mv_mode: str = "Q", overrides=None, probes: bool = False,
               rules_override=None):
    """Fit-compile one cell (+ optional roofline probes); result dict."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = cfg.supports_shape(shape)
    mesh_name = "multipod" if multi_pod else "pod"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "mv_mode": mv_mode, "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    pcfg = default_parallel(cfg, shape, mesh, overrides)
    rules = cell_rules(mesh, shape, pcfg, rules_override=rules_override)
    mvcfg = MVStoreConfig(enabled=True, mode=mv_mode)
    opt_cfg = adamw.AdamWConfig()

    compiled, times = compile_once(cfg, shape, mesh, pcfg, mvcfg, opt_cfg,
                                   rules)
    mem = compiled.memory_analysis()
    fit_metrics = _metrics(compiled)
    n_chips = mesh.devices.size

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mv_mode": mv_mode, "status": "ok", "n_chips": n_chips,
        "microbatches": pcfg.microbatches, "overrides": overrides or {},
        "rules_override": rules_override or {},
        **times,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "fit_metrics_scanned": {k: fit_metrics[k]
                                for k in ("flops", "bytes", "wire_bytes")},
        "collective_ops": fit_metrics["coll_ops"],
    }
    if probes and not multi_pod:
        recon, ledger = run_probes(arch, shape_name, mv_mode=mv_mode,
                                   overrides=overrides,
                                   rules_override=rules_override)
        result["probe_metrics"] = recon
        result["probe_ledger"] = ledger
        result["roofline"] = roofline.roofline_terms(
            cfg, shape,
            cost={"flops": recon["flops"],
                  "bytes accessed": recon["tpu_bytes"],
                  "bytes_raw": recon["bytes"]},
            collectives={"total_wire_bytes": recon["wire_bytes"]},
            n_chips=n_chips)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--mvmode", default="Q", choices=["Q", "U"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probes", action="store_true",
                    help="run roofline probes (single-pod cells only)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ParallelConfig overrides")
    ap.add_argument("--rules-override", default=None,
                    help="JSON dict of logical-axis rule overrides, e.g. "
                         "'{\"tp\": null}' for no tensor parallelism")
    args = ap.parse_args(argv)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    overrides = json.loads(args.override) if args.override else None
    rules_override = (json.loads(args.rules_override)
                      if args.rules_override else None)
    rc = 0
    for arch, shape, m in cells:
        try:
            res = lower_cell(arch, shape, multi_pod=(m == "multipod"),
                             mv_mode=args.mvmode, overrides=overrides,
                             probes=args.probes,
                             rules_override=rules_override)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            res = {"arch": arch, "shape": shape, "mesh": m,
                   "mv_mode": args.mvmode, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            rc = 1
        line = json.dumps(res)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        brief = {k: v for k, v in res.items()
                 if k not in ("trace", "probe_ledger", "collective_ops")}
        print(json.dumps(brief), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
