"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

From the device planes (``/device:TPU:<n>``):

* busy: the union of the intervals in which an operation ran, within
  the window, averaged over the chips that ran anything;
* kernel time by stable kernel name: the device time of the kernel's
  own operation (the Pallas custom call) inside the XLA programs that
  launch it, matched by the program's name (``KERNELS``);
* the operations that took most time, by program and operation.

From the host plane: the window (the benchmark's ``bench.window`` span)
and the benchmark's own ``bench.*`` spans, which label each of the
longest idle gaps with what the host was doing in it.

Host and device events share one time base in the trace.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

#: stable kernel name -> the XLA program (jitted wrapper) that runs it
KERNELS = {
    "gather_read": "_gather",
    "commit_fused": "_commit_fused_jit",
}
#: entries kept in each list of the breakdown
TOP = 10

_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def _program(name: str) -> str:
    """``jit__gather(123)`` -> ``_gather``: the jitted function's name."""
    name = _SUFFIX.sub("", name)
    return name[4:] if name.startswith("jit_") else name


def _op_name(name: str) -> str:
    """``%_gather.1 = s32[...] custom-call(...)`` -> ``_gather``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _is_kernel_op(name: str) -> bool:
    return "custom-call" in name or "custom_call" in name


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str):
    """(device planes, host spans) of one trace file.

    Device planes are lists of ``{"programs": [...], "ops": [...]}``;
    host spans are ``(name, start_ns, end_ns)`` of every host event."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {l.name: _events(l) for l in plane.lines}
            devices.append({"name": plane.name,
                            "programs": lines.get("XLA Modules", []),
                            "ops": lines.get("XLA Ops", [])})
        elif plane.name.startswith("/host:"):
            for l in plane.lines:
                host.extend(_events(l))
    return devices, host


def reduce_events(devices, host, window_name: str = "bench.window"
                  ) -> Dict:
    """The reduction proper, on loaded events (tested on a recorded
    trace).  Raises when the window span is missing."""
    spans = [h for h in host if h[0] == window_name]
    if not spans:
        raise ValueError(f"no {window_name!r} span in the trace")
    lo, hi = spans[0][1], spans[0][2]
    window_s = (hi - lo) * 1e-9
    busy_per_chip, kernels = [], collections.defaultdict(
        lambda: {"time_s": 0.0, "calls": 0})
    op_time = collections.Counter()
    busy_all: List[Tuple[float, float]] = []
    for dev in devices:
        ops = dev["ops"] or dev["programs"]
        iv = _union(_clip([(a, b) for _, a, b in ops], lo, hi))
        if not iv:
            continue
        busy_per_chip.append(sum(b - a for a, b in iv) * 1e-9)
        busy_all.extend(iv)
        progs = sorted((a, b, _program(n)) for n, a, b in dev["programs"]
                       if b > lo and a < hi)
        owner = _Owner(progs)
        for name, a, b in dev["ops"]:
            if b <= lo or a >= hi:
                continue
            prog = owner.at(a)
            dur = (min(b, hi) - max(a, lo)) * 1e-9
            op_time[f"{prog}:{_op_name(name)}"] += dur
            if not _is_kernel_op(name):
                continue
            for kernel, program in KERNELS.items():
                if prog == program:
                    kernels[kernel]["time_s"] += dur
                    kernels[kernel]["calls"] += 1
    busy_s = (sum(busy_per_chip) / len(busy_per_chip)
              if busy_per_chip else 0.0)
    gaps = _gaps(_union(busy_all), lo, hi)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "kernels": dict(kernels),
        "breakdown": {
            "device_ops": [[n, s] for n, s in op_time.most_common(TOP)],
            "idle_gaps": _label_gaps(gaps[:TOP], host, window_name),
        },
    }


class _Owner:
    """Which program an op at a time belongs to (programs sorted)."""

    def __init__(self, progs):
        self.progs = progs
        self.starts = [p[0] for p in progs]

    def at(self, t: float) -> str:
        import bisect
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.progs[i][1] >= t:
            return self.progs[i][2]
        return "?"


def _gaps(busy, lo, hi) -> List[Tuple[float, float]]:
    """Idle intervals in ``[lo, hi]``, longest first."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def _label_gaps(gaps, host, window_name) -> List[list]:
    """``[what the host was doing, seconds]`` for each gap: the
    benchmark spans that overlap it, or ``no bench span``."""
    spans = [h for h in host
             if h[0].startswith("bench.") and h[0] != window_name]
    out = []
    for a, b in gaps:
        names = sorted({n for n, s, e in spans if s < b and e > a})
        out.append(["+".join(names) or "no bench span", (b - a) * 1e-9])
    return out


def reduce_trace(trace_dir: str) -> Dict:
    """Reduce the newest trace under ``trace_dir``."""
    devices, host = load(find_xplane(trace_dir))
    return reduce_events(devices, host)
