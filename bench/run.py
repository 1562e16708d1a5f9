#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the configuration names
its workload kind (``bench/kinds/<kind>.py``: the system under test,
the one generator that reads the kind's traffic files, and the
comparison with its plain reference); per-layer metrics are readers in
``bench/metrics/<metric>.py``.  All are found by name, so a new cell,
mix, metric or kind of workload is new files and new entries.

A kind module has ``parse_traffic(params)``, ``control()`` (the
control's system factory, ``bench/control.py``) and ``Workload(config,
traffic, seed=, annotate=, make_system=)``, which builds the system
from the seed and has ``start``, ``wait_warm(timeout_s)``,
``progress()`` (what has committed), ``stop``, ``finish(timeout_s)``
(the system's counters), ``free``, ``record(t0, t1, stats)`` (what the
metric readers see, with ``attempted`` and ``failed``),
``end_to_end(t0, t1)`` and ``checks()`` (``{name: {"value",
"limit"}}``).

A run refuses any device but a TPU (exit 2, no result), builds the
system from the seed, warms up until the traffic file's warm-up has
run, measures for ``--seconds`` and prints one JSON line last on
standard output.  With ``--trace 0`` the line holds the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the line holds the per-layer metrics, ``busy_s``,
``window_s`` and a ``breakdown``.  After the window the system's state
is freed and the kind's plain reference decides ``correct``; the
numbers compared are printed beside their limits as the last lines on
standard error and under ``checks`` in the result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

#: seconds a warm-up may take before the run gives up
WARMUP_TIMEOUT_S = 240.0
#: seconds the traffic threads get to finish their last operation
DRAIN_TIMEOUT_S = 120.0


def process_age_s() -> float:
    """Seconds since this process started, from /proc."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# the cell, from its files
# ---------------------------------------------------------------------------


def load_module(path: Path):
    """The Python file ``path`` as a module of its own."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The workload entry ``name`` of ``BENCHMARK.json`` with its
    configuration, kind, traffic and metric declarations loaded."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    kind = load_module(root / "bench" / "kinds" / f"{config['kind']}.py")
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    traffic = kind.parse_traffic(json.loads(traffic_file.read_text()))

    def mine(metric):
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name, root=root, entry=cell, config=config, kind=kind,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)])


def load_metric_reader(name: str, root: Path = ROOT):
    """``read(rec)`` of ``bench/metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# device, programs, trace
# ---------------------------------------------------------------------------


def find_device(chips: int) -> dict:
    """The attached devices; exits 2 unless ``chips`` TPU chips exist."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform!r}); nothing "
              f"was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chips, found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Programs built (compiled, or loaded from the persistent cache)
    and, of those, cache hits, from JAX's monitoring events."""

    BUILT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.built = self.hits = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_built)
        monitoring.register_event_listener(self._on_hit)

    def _on_built(self, name, secs, **kw):
        if name == self.BUILT:
            self.built += 1

    def _on_hit(self, name, **kw):
        if name == self.HIT:
            self.hits += 1

    def read(self) -> tuple:
        return self.built, self.hits


def use_cache() -> str:
    """The program's persistent compilation cache, every program kept."""
    import jax
    from repro.runtime.compile_cache import use_compile_cache
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


@contextlib.contextmanager
def traced(enabled: bool):
    """Run the body under the JAX profiler; yields the trace directory
    (``None`` when not tracing).  The directory is removed afterwards."""
    if not enabled:
        yield None
        return
    import jax
    where = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(where, profiler_options=opts)
    try:
        yield where
    finally:
        jax.profiler.stop_trace()


def annotator(enabled: bool):
    if not enabled:
        return None
    import jax
    return jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             make_system=None, device=None) -> dict:
    """Drive one cell; returns the result line as a dict.

    ``make_system`` builds the system under test in the kind's own
    terms (the kind's program system by default); ``device`` is the
    device dict (looked up and required to be a TPU by ``main``)."""
    import jax

    compiles = CompileCounter()
    t_start = time.perf_counter()
    work = cell.kind.Workload(cell.config, cell.traffic, seed=seed,
                              annotate=annotator(trace),
                              make_system=make_system)
    t_system = time.perf_counter()
    work.start()
    work.wait_warm(WARMUP_TIMEOUT_S)
    t_warm = time.perf_counter()
    built_warm, hits_warm = compiles.read()
    print(f"bench: set-up system {t_system - t_start:.3f} s, traffic "
          f"{t_warm - t_system:.3f} s; warm-up {work.progress()}",
          file=sys.stderr)
    with traced(trace) as trace_dir:
        ann = annotator(trace)
        with (ann("bench.window") if ann else contextlib.nullcontext()):
            t0 = time.perf_counter()
            setup_s = process_age_s()
            time.sleep(seconds)
            t1 = time.perf_counter()
        work.stop()
        built, hits = compiles.read()
    stats = work.finish(DRAIN_TIMEOUT_S)
    mem = (jax.devices()[0].memory_stats() or {}) if device else {}
    work.free()
    gc.collect()

    rec = work.record(t0, t1, stats)
    print(f"bench: programs built in window {built - built_warm} "
          f"({hits - hits_warm} from the cache), during set-up "
          f"{built_warm} ({hits_warm} from the cache); system stats "
          f"{json.dumps(stats, default=str)}",
          file=sys.stderr)
    metrics = {}
    dev = dict(device or {"platform": "none", "kind": "none", "count": 0})
    dev["memory_peak_bytes"] = mem.get("peak_bytes_in_use")
    breakdown = None
    if trace:
        from trace_reduce import reduce_trace
        rec.trace = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.device_kind = dev["kind"]
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        breakdown = rec.trace["breakdown"]
        for m in cell.per_layer:
            v = load_metric_reader(m["name"], cell.root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = work.end_to_end(t0, t1)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    checks = work.checks()
    print(f"bench: checked {work.progress()}", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": rec.attempted,
            "failed": rec.failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    use_cache()
    device = find_device(cell.entry["chips"])
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), device=device)
    print_result(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
