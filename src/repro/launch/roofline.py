"""Roofline terms from a compiled dry-run artifact.

Hardware model: the per-chip peaks in ``PEAKS``, keyed by the device
kind JAX reports; the dry-run targets ``DRYRUN_DEVICE_KIND`` (TPU v5e).

cost_analysis() of the SPMD-partitioned executable reports *per-device*
flops and bytes.  Collective bytes are NOT in cost_analysis: we parse the
post-optimization HLO and sum wire bytes per collective op, converting each
op's result shape to bytes-on-the-wire with the standard ring-algorithm
factors (all-reduce moves 2x(n-1)/n of the tensor, all-gather and
reduce-scatter (n-1)/n of the *full* tensor, all-to-all (n-1)/n, permute
1x).  See EXPERIMENTS.md SSRoofline for the caveats.
"""
from __future__ import annotations

import re
from typing import Dict

#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM
#: bandwidth and 1,600 Gbit/s of interconnect per chip, i.e. 50 GB/s on
#: each of its four ICI links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: the chip the dry-run models
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """The ``PEAKS`` row for ``device_kind``; a device missing from the
    table is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# `%x = f32[128,1024]{1,0} all-reduce(...)`, possibly tuple-typed
_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(typestr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(typestr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return default


_WIRE_FACTOR = {
    # multiplier applied to the op's RESULT bytes to estimate per-device
    # wire traffic, assuming ring algorithms over a group of size n
    "all-reduce": lambda n: 2.0 * (n - 1) / max(n, 1),
    "all-gather": lambda n: (n - 1) / max(n, 1),
    "reduce-scatter": lambda n: float(n - 1),   # result is 1/n of operand
    "all-to-all": lambda n: (n - 1) / max(n, 1),
    "collective-permute": lambda n: 1.0,
}


def collective_bytes(compiled_or_text, default_group: int = 1,
                     top_k: int = 8) -> Dict:
    """Parse the post-SPMD HLO; per-op-kind result-bytes and wire-bytes,
    plus the top-K largest collectives (shape + group) for debugging."""
    if isinstance(compiled_or_text, str):
        text = compiled_or_text
    else:
        try:
            text = compiled_or_text.as_text()
        except Exception:  # pragma: no cover
            return {"total_result_bytes": 0, "total_wire_bytes": 0,
                    "ops": {}, "top": []}
    ops: Dict[str, Dict[str, float]] = {}
    total_wire = 0.0
    total_res = 0
    top = []
    for line in text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        typestr, kind = m.group(1), m.group(2)
        # async pairs appear as -start/-done; count the -start only
        if f"{kind}-done" in line:
            continue
        nbytes = _shape_bytes(typestr)
        if f"{kind}-start" in line:
            # start ops have tuple types (operand, result, ...): halve
            nbytes = nbytes // 2 if nbytes else nbytes
        n = _group_size(line, default_group)
        wire = nbytes * _WIRE_FACTOR[kind](n)
        d = ops.setdefault(kind, {"count": 0, "result_bytes": 0,
                                  "wire_bytes": 0.0})
        d["count"] += 1
        d["result_bytes"] += nbytes
        d["wire_bytes"] += wire
        total_wire += wire
        total_res += nbytes
        top.append((wire, kind, typestr.strip()[:120], n))
    top.sort(reverse=True)
    return {"total_result_bytes": total_res,
            "total_wire_bytes": total_wire, "ops": ops,
            "top": [{"wire_bytes": w, "kind": k, "type": t, "group": n}
                    for w, k, t, n in top[:top_k]]}


# ---------------------------------------------------------------------------
# TPU-realistic HBM bytes model (edge materialization)
# ---------------------------------------------------------------------------
#
# XLA:CPU fuses far less than XLA:TPU, so the raw 'bytes accessed' of the
# CPU-compiled artifact counts every elementwise intermediate as HBM
# traffic.  For the memory roofline term we instead simulate TPU-grade
# fusion on the optimized HLO's dataflow edges: an edge (producer ->
# consumer) moves HBM bytes iff at least one endpoint is NON-fusable
# (dot/conv/reduce/gather/scatter/sort/collective/parameter/while/...).
# Edges between fusable ops (fusions, bare elementwise, broadcasts,
# converts, reshapes) collapse — the TPU fuser would keep them in VMEM.
# Program outputs are charged once.  The raw cost-analysis number is kept
# alongside as the no-fusion upper bound (EXPERIMENTS.md SSRoofline).

_FUSABLE = {
    "fusion", "broadcast", "constant", "iota", "convert", "reshape",
    "bitcast", "get-tuple-element", "tuple", "copy", "add", "subtract",
    "multiply", "divide", "maximum", "minimum", "exponential", "log",
    "negate", "abs", "sign", "compare", "select", "and", "or", "not",
    "xor", "power", "rsqrt", "sqrt", "tanh", "floor", "ceil",
    "round-nearest-afz", "is-finite", "clamp", "pad", "slice",
    "concatenate", "transpose", "reverse", "reduce-precision",
    "exponential-minus-one", "log-plus-one", "logistic", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "remainder",
    "partition-id", "replica-id", "after-all",
}
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[^)]*?)\s*"
    r"([a-z][\w\-]*)\(([^)]*)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def tpu_bytes_model(compiled_or_text) -> Dict:
    """Fusion-collapsed HBM byte estimate from optimized HLO text."""
    if isinstance(compiled_or_text, str):
        text = compiled_or_text
    else:
        try:
            text = compiled_or_text.as_text()
        except Exception:  # pragma: no cover
            return {"tpu_bytes": 0.0}
    lines = text.splitlines()
    # computation spans; fusion bodies are interior (skipped)
    comp_of_line = []
    current = None
    for ln in lines:
        s = ln.strip()
        if s.endswith("{") and ("%" in s or s.startswith("ENTRY")):
            current = s.split("{")[0].strip()
        comp_of_line.append(current)
    fused_bodies = set()
    shapes: Dict[str, int] = {}
    producer_op: Dict[str, str] = {}
    for ln in lines:
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        name, typestr, op = m.group(1), m.group(2), m.group(3)
        shapes[name] = _shape_bytes(typestr)
        producer_op[name] = op
        if op == "fusion":
            mm = re.search(r"calls=%?([\w.\-]+)", ln)
            if mm:
                fused_bodies.add(mm.group(1))
    total = 0.0
    root_bytes = 0
    materialized_writes = set()       # fusable producers read by non-fusable
    entries = []
    for ln, comp in zip(lines, comp_of_line):
        if comp and any(fb in comp for fb in fused_bodies):
            continue
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        entries.append((ln, m))
        name, typestr, op, operands = m.groups()
        if op not in _FUSABLE:
            for o in _OPERAND_RE.findall(operands):
                materialized_writes.add(o)
    for ln, m in entries:
        name, typestr, op, operands = m.groups()
        consumer_fusable = op in _FUSABLE
        # reads: materialized edges
        for o in _OPERAND_RE.findall(operands):
            if o not in shapes:
                continue
            pop = producer_op.get(o, "parameter")
            if consumer_fusable and pop in _FUSABLE:
                continue                      # stays in VMEM
            total += shapes[o]
        # writes: every non-fusable op writes its result (parameters are
        # inputs, not writes — their reads are counted at consumer edges);
        # a fusable chain's result is written once iff some non-fusable op
        # reads it
        if (op not in _FUSABLE and op != "parameter") or \
                (op in _FUSABLE and name in materialized_writes):
            total += shapes.get(name, 0)
        if ln.strip().startswith("ROOT"):
            root_bytes = shapes.get(name, 0)
    total += root_bytes
    return {"tpu_bytes": total}


def attention_score_bytes(compiled_or_text, block_q: int = 1024,
                          block_k: int = 1024) -> float:
    """HBM bytes attributable to materialized attention score/softmax
    tiles ([..., bq, bk] tensors at non-fusable edge endpoints).

    The XLA blockwise-attention lowering materializes these per pair-step;
    the Pallas flash kernel (kernels/flash_attention.py) keeps them in
    VMEM.  Subtracting this from tpu_bytes models deploying the kernel on
    TPU — used for the kernel-credit rows of EXPERIMENTS.md SSPerf.
    """
    if isinstance(compiled_or_text, str):
        text = compiled_or_text
    else:
        try:
            text = compiled_or_text.as_text()
        except Exception:  # pragma: no cover
            return 0.0
    total = 0.0
    suffixes = {f"{block_q},{block_k}]", f"{block_k},{block_q}]"}
    for ln in text.splitlines():
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        name, typestr, op, operands = m.groups()
        if op != "dot":
            continue
        ts = typestr.replace(" ", "").split("{")[0]
        # score-shaped dot outputs (fwd s, bwd ds/dp): each materializes
        # once and is re-read once by its consumer dot through the
        # (fused) softmax chain
        if any(ts.endswith(sfx) for sfx in suffixes):
            total += 2 * _shape_bytes(typestr)
    return total


def roofline_terms(cfg, shape, *, cost: Dict, collectives: Dict,
                   n_chips: int,
                   device_kind: str = DRYRUN_DEVICE_KIND) -> Dict:
    """The three terms (seconds) + MODEL_FLOPS ratio for one cell."""
    from repro.models.model_zoo import model_flops

    peak = peaks(device_kind)
    flops_dev = float(cost.get("flops") or 0.0)
    bytes_dev = float(cost.get("bytes accessed") or 0.0)
    wire_dev = float(collectives.get("total_wire_bytes") or 0.0)
    t_compute = flops_dev / peak["flops"]
    t_memory = bytes_dev / peak["hbm_bw"]
    # per assignment: collective_bytes / (chips * link_bw), with
    # collective_bytes global = per-device wire * chips -> simplifies to
    # per-device wire / link_bw
    t_coll = wire_dev / peak["ici_bw"]
    mf = model_flops(cfg, shape)
    hlo_global = flops_dev * n_chips
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    total = max(t_compute, t_memory, t_coll)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": (mf / hlo_global) if hlo_global else 0.0,
        "roofline_fraction": (
            (mf / (n_chips * peak["flops"])) / total if total else 0.0),
    }
