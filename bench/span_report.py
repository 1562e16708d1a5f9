#!/usr/bin/env python3
"""Where a traced run's time goes, read from the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--save <file.xplane.pb>]

Runs the cell as ``bench/run.py --trace 1`` does (``run.run_cell``,
unchanged) and, before the harness removes its trace, reduces the
program's spans (``repro.runtime.tracing``: names under ``PROGRAM``) in
it.  Prints the harness's result line, then the report as one JSON line
last on standard output:

* ``by_tid``: for each transaction thread (the ``tid`` its ``tm.attempt``
  spans carry), seconds in attempts, in aborted attempts and in each
  program span inside them, and attempts by ``outcome``;
* ``read_bulk_words``: words the ``mvstore.read_bulk`` spans asked for,
  in all attempts and in committed ones;
* ``gather_read``: launches, words and host bytes uploaded
  (``kernel.gather_read``'s ``n`` and ``h2d_bytes``);
* ``idle_by_span``: the window's device-idle seconds put down to the
  innermost program span open on some thread (a name counts once however
  many threads hold it, so names overlap), and ``no program span``;
* ``idle_gaps``: the longest idle gaps, each labelled ``<bench spans> |
  <innermost program spans, most time first>``;
* ``clock``: whether host spans and device operations share a clock.
  Each ``_gather`` device program is followed back along the trace's
  flow links (``_c`` consumer, ``_p`` producer) to the host call that
  dispatched it, and so to the ``kernel.gather_read`` span that
  launched it and the ``mvstore.read_bulk`` span around that; the
  shares count launches whose span starts no later than the program and
  reads whose span ends no earlier than it.  The link is found on the
  host's clock alone, so the check does not assume what it tests.

Program spans are clipped to the ``bench.window`` span; the clock check
takes every linked launch in the trace.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, NamedTuple, Tuple

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

#: name prefixes of the program's own spans
PROGRAM = ("tm.", "mvstore.", "mvcontroller.", "kernel.")
WINDOW = "bench.window"
#: flow links followed back from a device program at most
MAX_HOPS = 16
#: consumers scanned back on a line for the one enclosing an event
MAX_SCAN = 64


class Event(NamedTuple):
    """A host event: its trace line (one per thread), start and end in
    ns, and its stats (span args, flow ids)."""

    name: str
    line: int
    start: float
    end: float
    stats: dict


class Program(NamedTuple):
    """A device program: its jitted function's name, start and end in
    ns, and the flow id that links it to its dispatch (``None`` when
    the trace has none)."""

    name: str
    start: float
    end: float
    flow: object


def load(path: str) -> Tuple[List[Event], List[Program], List[tuple]]:
    """(host events, device programs, device op intervals) of one trace
    file; stats are read for the program's spans and flow-carrying
    events only."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    host, programs, ops = [], [], []
    line = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for l in plane.lines:
                if l.name == "XLA Modules":
                    for e in l.events:
                        programs.append(Program(
                            trace_reduce._program(e.name), float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                            dict(e.stats).get("_c")))
                elif l.name == "XLA Ops":
                    ops.extend((float(e.start_ns),
                                float(e.start_ns + e.duration_ns))
                               for e in l.events)
        elif plane.name.startswith("/host:"):
            for l in plane.lines:
                for e in l.events:
                    stats = dict(e.stats)
                    if not (e.name.startswith(PROGRAM + ("bench.",))
                            or "_p" in stats or "_c" in stats):
                        continue
                    host.append(Event(e.name, line, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns),
                                      stats))
                line += 1
    if not ops:                       # a trace without op lines
        ops = [(p.start, p.end) for p in programs]
    return host, programs, ops


# ---------------------------------------------------------------------------
# spans by thread
# ---------------------------------------------------------------------------


def window(host: List[Event]) -> Tuple[float, float]:
    for e in host:
        if e.name == WINDOW:
            return e.start, e.end
    raise ValueError(f"no {WINDOW!r} span in the trace")


def clipped(host: List[Event], lo: float, hi: float) -> List[Event]:
    """The program's spans that overlap ``[lo, hi]``, clipped to it."""
    return [e._replace(start=max(e.start, lo), end=min(e.end, hi))
            for e in host
            if e.name.startswith(PROGRAM) and e.end > lo and e.start < hi]


class Nest:
    """Spans of one line sorted by start, to find the one holding
    another: the innermost, since spans on a line nest."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]

    def around(self, inner: Event):
        """The innermost span holding ``inner`` (``inner`` itself if it
        is one), or ``None``; looks ``MAX_SCAN`` spans back at most."""
        i = bisect.bisect_right(self.starts, inner.start) - 1
        for j in range(i, max(-1, i - MAX_SCAN), -1):
            if self.spans[j].end >= inner.end:
                return self.spans[j]
        return None


def _nests(spans, keep) -> Dict[int, Nest]:
    """A ``Nest`` per line of the spans that ``keep``."""
    lines = collections.defaultdict(list)
    for s in spans:
        if keep(s):
            lines[s.line].append(s)
    return collections.defaultdict(lambda: Nest([]),
                                   {k: Nest(v) for k, v in lines.items()})


def by_tid(spans: List[Event]) -> Dict:
    """Per transaction thread: attempt seconds, aborted-attempt seconds,
    seconds in each program span inside its attempts, and attempts by
    outcome.  Lines whose attempts carry more than one ``tid`` are
    listed under ``mixed_lines``."""
    attempts = _nests(spans, lambda s: s.name == "tm.attempt")
    out, mixed = {}, []
    for line, nest in list(attempts.items()):
        tids = {a.stats.get("tid") for a in nest.spans}
        if len(tids) > 1:
            mixed.append(line)
        t = out.setdefault(str(nest.spans[0].stats.get("tid")), {
            "attempt_s": 0.0, "aborted_s": 0.0,
            "span_s": collections.Counter(),
            "outcomes": collections.Counter()})
        for a in nest.spans:
            d = (a.end - a.start) * 1e-9
            outcome = str(a.stats.get("outcome", ""))
            t["attempt_s"] += d
            t["aborted_s"] += d if outcome.startswith("abort") else 0.0
            t["outcomes"][outcome] += 1
        for s in spans:
            if s.line == line and s.name != "tm.attempt" \
                    and nest.around(s) is not None:
                t["span_s"][s.name] += (s.end - s.start) * 1e-9
    for t in out.values():
        t["span_s"] = dict(t["span_s"].most_common())
        t["outcomes"] = dict(t["outcomes"].most_common())
    return {"by_tid": dict(sorted(out.items())), "mixed_lines": mixed}


def read_bulk_words(spans: List[Event]) -> Dict:
    """Words asked for by ``mvstore.read_bulk`` spans, in all attempts
    and in committed ones."""
    attempts = _nests(spans, lambda s: s.name == "tm.attempt")
    every = committed = 0
    for s in spans:
        if s.name != "mvstore.read_bulk":
            continue
        w = int(s.stats.get("words", 0))
        every += w
        a = attempts[s.line].around(s)
        if a is not None and a.stats.get("outcome") == "commit":
            committed += w
    return {"all": every, "committed": committed}


def gather_read(spans: List[Event]) -> Dict:
    ks = [s for s in spans if s.name == "kernel.gather_read"]
    n = sum(int(s.stats.get("n", 0)) for s in ks)
    h2d = sum(int(s.stats.get("h2d_bytes", 0)) for s in ks)
    return {"launches": len(ks), "words": n, "h2d_bytes": h2d,
            "h2d_bytes_per_word": h2d / n if n else None}


# ---------------------------------------------------------------------------
# idle time
# ---------------------------------------------------------------------------


def innermost(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` pieces of time in which ``name`` is the
    innermost program span open on its line, over all lines (spans on
    one line nest)."""
    lines = collections.defaultdict(list)
    for s in spans:
        lines[s.line].append(s)
    out = []
    for group in lines.values():
        group.sort(key=lambda s: (s.start, -s.end))
        stack: List[Event] = []
        t = 0.0
        for s in group:
            while stack and stack[-1].end <= s.start:
                top = stack.pop()
                out.append((t, top.end, top.name))
                t = max(t, top.end)
            if stack:
                out.append((t, s.start, stack[-1].name))
            stack.append(s)
            t = s.start
        while stack:
            top = stack.pop()
            out.append((t, top.end, top.name))
            t = max(t, top.end)
    return [p for p in out if p[1] > p[0]]


def _overlap_s(xs, ys) -> float:
    """Seconds in both of two sorted lists of disjoint ns intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def idle_by_span(gaps, pieces) -> List[list]:
    """``[name, idle seconds]`` for each program span name, the idle
    time in which it was the innermost open span on some line, and
    ``no program span`` for the idle time in which none was open."""
    idle = sorted(gaps)
    by_name = collections.defaultdict(list)
    for a, b, name in pieces:
        by_name[name].append((a, b))
    out = [[name, _overlap_s(trace_reduce._union(iv), idle)]
           for name, iv in by_name.items()]
    covered = _overlap_s(
        trace_reduce._union([(a, b) for a, b, _ in pieces]), idle)
    out.append(["no program span",
                sum(b - a for a, b in idle) * 1e-9 - covered])
    return sorted(out, key=lambda x: -x[1])


def label_gaps(gaps, host: List[Event], pieces) -> List[list]:
    """``[label, seconds]`` for each gap: the benchmark spans that
    overlap it (or ``no bench span``), then after `` | `` the innermost
    program spans open during it, most time first (or ``no program
    span``)."""
    bench = [e for e in host
             if e.name.startswith("bench.") and e.name != WINDOW]
    out = []
    for a, b in gaps:
        names = sorted({e.name for e in bench if e.start < b and e.end > a})
        held = collections.Counter()
        for s, e, name in pieces:
            if s < b and e > a:
                held[name] += min(e, b) - max(s, a)
        program = "+".join(n for n, _ in held.most_common())
        out.append([f"{'+'.join(names) or 'no bench span'} | "
                    f"{program or 'no program span'}", (b - a) * 1e-9])
    return out


# ---------------------------------------------------------------------------
# the shared clock
# ---------------------------------------------------------------------------


def dispatch_point(flow, producers, consumers):
    """The host event from which a device program was dispatched: follow
    the flow back to its producer, then the flow that the innermost
    event around the producer on its line consumed, until no flow leads
    further.  ``None`` when the first link is missing."""
    e = producers.get(flow)
    if e is None:
        return None
    for _ in range(MAX_HOPS):
        enc = consumers[e.line].around(e)
        if enc is None or enc.stats["_c"] not in producers:
            return e
        e = producers[enc.stats["_c"]]
    return e


def clock(host: List[Event], programs: List[Program],
          program: str = "_gather") -> Dict:
    """The shared-clock check for the ``program`` launches (module
    docstring); ``enqueue_*`` compares each program with the host event
    that its own flow link names."""
    producers = {e.stats["_p"]: e for e in host if "_p" in e.stats}
    consumers = _nests(host, lambda e: "_c" in e.stats)
    kernels = Nest(e for e in host if e.name == "kernel.gather_read")
    reads = _nests(host, lambda e: e.name == "mvstore.read_bulk")
    mine = [p for p in programs if p.name == program]
    linked, leads, tails, enqueue_leads = 0, [], [], []
    for p in mine:
        d = dispatch_point(p.flow, producers, consumers)
        if d is None:
            continue
        linked += 1
        enqueue_leads.append(p.start - producers[p.flow].start)
        # the launch span open at the dispatch, found on the host clock
        i = bisect.bisect_right(kernels.starts, d.start) - 1
        if i < 0 or kernels.spans[i].end < d.start:
            continue
        k = kernels.spans[i]
        leads.append(p.start - k.start)
        r = reads[k.line].around(k)
        if r is not None:
            tails.append(r.end - p.end)

    def share(xs):
        return sum(x >= 0 for x in xs) / len(xs) if xs else None

    def ms(xs, f):
        return f(xs) * 1e-6 if xs else None

    return {"programs": len(mine), "linked": linked,
            "with_kernel_span": len(leads), "with_read_bulk": len(tails),
            "kernel_span_starts_first": share(leads),
            "read_bulk_ends_after": share(tails),
            "enqueue_before_program": share(enqueue_leads),
            "lead_ms_min": ms(leads, min), "tail_ms_min": ms(tails, min),
            "enqueue_lead_ms_min": ms(enqueue_leads, min),
            "enqueue_lead_ms_max": ms(enqueue_leads, max)}


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


def report(host: List[Event], programs: List[Program], ops) -> Dict:
    """The whole report (module docstring) of one loaded trace."""
    lo, hi = window(host)
    spans = clipped(host, lo, hi)
    busy = trace_reduce._union(trace_reduce._clip(ops, lo, hi))
    gaps = trace_reduce._gaps(busy, lo, hi)
    pieces = innermost(spans)
    out = {"window_s": (hi - lo) * 1e-9,
           "busy_s": sum(b - a for a, b in busy) * 1e-9,
           "spans": len(spans)}
    out.update(by_tid(spans))
    out["read_bulk_words"] = read_bulk_words(spans)
    out["gather_read"] = gather_read(spans)
    out["idle_by_span"] = idle_by_span(gaps, pieces)
    out["idle_gaps"] = label_gaps(gaps[:trace_reduce.TOP], host, pieces)
    out["clock"] = clock(host, programs)
    return out


@contextlib.contextmanager
def reducing_spans(into: dict, save: str = None):
    """While the body runs, the harness's trace reduction also puts this
    report under ``into["report"]`` (and copies the trace to ``save``)
    before the harness removes the trace."""
    plain = trace_reduce.reduce_trace

    def both(trace_dir):
        path = trace_reduce.find_xplane(trace_dir)
        if save:
            shutil.copy(path, save)
        t = time.perf_counter()
        into["report"] = report(*load(path))
        print(f"span_report: reduced {os.path.getsize(path)} bytes of "
              f"trace in {time.perf_counter() - t:.1f} s", file=sys.stderr)
        return plain(trace_dir)

    trace_reduce.reduce_trace = both
    try:
        yield into
    finally:
        trace_reduce.reduce_trace = plain


def traced_report(cell, *, seed: int, seconds: float, device=None,
                  save: str = None) -> Tuple[dict, dict]:
    """(the harness's traced result line, the span report) of one run."""
    import run as bench_run
    got: dict = {}
    with reducing_spans(got, save):
        line = bench_run.run_cell(cell, seed=seed, seconds=seconds,
                                  trace=True, device=device)
    return line, got["report"]


def main(argv=None) -> int:
    import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", help="copy the trace file here")
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    bench_run.use_cache()
    device = bench_run.find_device(cell.entry["chips"])
    line, rep = traced_report(cell, seed=args.seed, seconds=args.seconds,
                              device=device, save=args.save)
    bench_run.print_result(line)
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
