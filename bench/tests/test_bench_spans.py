"""The span report (``bench/span_report.py``): attempt time by thread,
idle time by program span, and the shared-clock check, on synthetic
events, on the trace recorded on one TPU v5e (``data/small.xplane.pb``,
recorded before the program had spans) and on a traced CPU run."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import span_report as sr  # noqa: E402
import trace_reduce  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "small.xplane.pb"
MS = 1e6        # one millisecond in the trace's ns


def _ev(name, line, start_ms, end_ms, **stats):
    return sr.Event(name, line, start_ms * MS, end_ms * MS, stats)


SPANS = [
    # line 1, tid 1: a transfer aborted on a conflict [0, 10), then one
    # committed [10, 50): reads 2 + 5 + 10 + 3 ms, a 4 ms lock wait,
    # 6 ms publish
    _ev("tm.attempt", 1, 0, 10, tid=1, outcome="abort.commit_conflict"),
    _ev("mvstore.read", 1, 0, 2, path="live"),
    _ev("mvstore.read", 1, 2, 7, path="live"),
    _ev("tm.attempt", 1, 10, 50, tid=1, outcome="commit"),
    _ev("mvstore.read", 1, 10, 20, path="live"),
    _ev("mvstore.read", 1, 20, 23, path="live"),
    _ev("mvstore.commit.lock_wait", 1, 30, 34),
    _ev("mvstore.publish", 1, 34, 40, words=2),
    # line 2, tid 0: an audit aborted on a donation race [0, 30), a
    # committed one [30, 100), each asking for 1,000 words
    _ev("tm.attempt", 2, 0, 30, tid=0, outcome="abort.donation_race"),
    _ev("mvstore.read_bulk", 2, 1, 29, words=1000, path="ring"),
    _ev("kernel.gather_read", 2, 2, 3, n=1000, h2d_bytes=4096),
    _ev("tm.attempt", 2, 30, 100, tid=0, outcome="commit"),
    _ev("mvstore.read_bulk", 2, 31, 99, words=1000, path="ring"),
    _ev("kernel.gather_read", 2, 32, 33, n=1000, h2d_bytes=4096),
    # line 3: the controller, outside any attempt
    _ev("mvcontroller.step", 3, 0, 100),
]


def test_attempt_time_by_thread():
    out = sr.by_tid(SPANS)
    assert out["mixed_lines"] == []
    t = out["by_tid"]["1"]
    assert t["attempt_s"] == pytest.approx(0.050)
    assert t["aborted_s"] == pytest.approx(0.010)
    assert t["span_s"]["mvstore.read"] == pytest.approx(0.020)
    assert t["span_s"]["mvstore.commit.lock_wait"] == pytest.approx(0.004)
    assert t["span_s"]["mvstore.publish"] == pytest.approx(0.006)
    assert t["outcomes"] == {"abort.commit_conflict": 1, "commit": 1}
    a = out["by_tid"]["0"]
    assert a["aborted_s"] / a["attempt_s"] == pytest.approx(0.3)
    assert a["outcomes"] == {"abort.donation_race": 1, "commit": 1}
    # the controller runs no attempts, so it is no transaction thread
    assert set(out["by_tid"]) == {"0", "1"}


def test_a_line_with_two_tids_is_named():
    two = SPANS + [_ev("tm.attempt", 1, 60, 70, tid=2, outcome="commit")]
    assert sr.by_tid(two)["mixed_lines"] == [1]


def test_words_asked_for_and_bytes_uploaded():
    assert sr.read_bulk_words(SPANS) == {"all": 2000, "committed": 1000}
    g = sr.gather_read(SPANS)
    assert g["launches"] == 2 and g["words"] == 2000
    assert g["h2d_bytes_per_word"] == pytest.approx(8192 / 2000)
    assert sr.gather_read([])["h2d_bytes_per_word"] is None


def test_spans_are_clipped_to_the_window():
    host = [_ev("bench.window", 0, 100, 200),
            _ev("tm.attempt", 0, 50, 150, outcome="commit"),
            _ev("tm.attempt", 0, 180, 300, outcome="abort.clock"),
            _ev("tm.attempt", 0, 10, 90, outcome="commit"),
            _ev("bench.transfer", 0, 0, 300)]
    lo, hi = sr.window(host)
    got = sr.clipped(host, lo, hi)
    assert [(s.start / MS, s.end / MS) for s in got] == [(100, 150),
                                                        (180, 200)]
    with pytest.raises(ValueError):
        sr.window(host[1:])


def test_program_spans_label_the_idle_time():
    # line 1: an attempt [100, 900] holding a read [150, 500] and a
    # publish [600, 850]; line 2: one controller step [200, 300];
    # the device is busy [400, 700] of the window [0, 1000]
    ns = 1e-6
    host = [_ev("bench.window", 0, 0, 1000 * ns),
            _ev("bench.transfer", 0, 50 * ns, 950 * ns),
            _ev("tm.attempt", 1, 100 * ns, 900 * ns, tid=3,
                outcome="commit"),
            _ev("mvstore.read", 1, 150 * ns, 500 * ns, path="live"),
            _ev("mvstore.publish", 1, 600 * ns, 850 * ns, words=2),
            _ev("mvcontroller.step", 2, 200 * ns, 300 * ns)]
    out = sr.report(host, [], [(400.0, 700.0)])
    assert out["busy_s"] == pytest.approx(300e-9)
    gaps = out["idle_gaps"]
    # idle [0, 400]: read 250, step 100, attempt 50; [700, 1000]:
    # publish 150, attempt 50, then nothing for 100
    assert gaps[0] == ["bench.transfer | mvstore.read+mvcontroller.step"
                       "+tm.attempt", pytest.approx(400e-9)]
    assert gaps[1] == ["bench.transfer | mvstore.publish+tm.attempt",
                       pytest.approx(300e-9)]
    idle = dict(out["idle_by_span"])
    assert idle["mvstore.read"] == pytest.approx(250e-9)
    assert idle["mvstore.publish"] == pytest.approx(150e-9)
    assert idle["mvcontroller.step"] == pytest.approx(100e-9)
    assert idle["tm.attempt"] == pytest.approx(100e-9)
    # [0, 100] and [900, 1000]: no line has a program span open
    assert idle["no program span"] == pytest.approx(200e-9)


def _launch(first_ms, program_ms, flow):
    """The host side of one gather launch at ``first_ms``: the read and
    the kernel span on the Python line 1, the dispatch that produces
    flow ``flow``, the runtime's call on line 2 that consumes it and
    hands on flow ``flow + 1``, and the enqueue on line 3 that produces
    the device program's flow ``flow + 2``; and the program itself."""
    t = first_ms
    host = [_ev("mvstore.read_bulk", 1, t, t + 20, words=8, path="ring"),
            _ev("kernel.gather_read", 1, t + 1, t + 3, n=8,
                h2d_bytes=32),
            _ev("linkage", 1, t + 2, t + 2.1, _p=flow),
            _ev("execute", 2, t + 2.05, t + 2.5, _c=flow),
            _ev("system.execute", 2, t + 2.2, t + 2.3, _p=flow + 1),
            _ev("sequenced", 3, t + 2.4, t + 2.8, _c=flow + 1),
            _ev("enqueue", 3, t + 2.5, t + 2.6, _p=flow + 2)]
    prog = sr.Program("_gather", program_ms * MS, (program_ms + 5) * MS,
                      flow + 2)
    return host, prog


def test_each_launch_is_found_by_its_flow_links():
    h1, p1 = _launch(0, 4, 10)         # program after its launch
    h2, p2 = _launch(40, 40.5, 20)     # the device clock 0.5 ms early
    h3, p3 = _launch(80, 97, 30)       # ends after its read: late clock
    out = sr.clock(h1 + h2 + h3,
                   [p1, p2, p3, sr.Program("_gather", 0, 1, None),
                    sr.Program("_other", 0, 1, 10)])
    assert out["programs"] == 4 and out["linked"] == 3
    assert out["with_kernel_span"] == 3 and out["with_read_bulk"] == 3
    assert out["kernel_span_starts_first"] == pytest.approx(2 / 3)
    assert out["read_bulk_ends_after"] == pytest.approx(2 / 3)
    assert out["lead_ms_min"] == pytest.approx(-0.5)
    assert out["tail_ms_min"] == pytest.approx(-2)
    assert out["enqueue_before_program"] == pytest.approx(2 / 3)


def test_recorded_trace_links_each_gather_to_its_python_call():
    """The v5e recording: the three ``_gather`` programs link back to
    the Python thread's dispatch, the window and busy time read as the
    harness's reduction reads them, and the device program starts
    about 1.2 ms before the host event that enqueued it."""
    host, programs, ops = sr.load(str(RECORDED))
    out = sr.report(host, programs, ops)
    plain = trace_reduce.reduce_trace(str(RECORDED.parent))
    assert out["window_s"] == pytest.approx(plain["window_s"])
    assert out["busy_s"] == pytest.approx(plain["busy_s"])
    assert [g[0].split(" | ")[0] for g in out["idle_gaps"]] == [
        g[0] for g in plain["breakdown"]["idle_gaps"]]
    assert out["spans"] == 0
    assert dict(out["idle_by_span"]) == {
        "no program span": pytest.approx(plain["window_s"]
                                         - plain["busy_s"])}
    c = out["clock"]
    assert c["programs"] == 3 and c["linked"] == 3
    assert c["with_kernel_span"] == 0      # recorded without spans
    producers = {e.stats["_p"]: e for e in host if "_p" in e.stats}
    consumers = sr._nests(host, lambda e: "_c" in e.stats)
    python = {e.line for e in host if e.name == "bench.window"}
    for p in programs:
        if p.name == "_gather":
            d = sr.dispatch_point(p.flow, producers, consumers)
            assert d.line in python
    assert c["enqueue_lead_ms_max"] == pytest.approx(-1.18, abs=0.01)


def test_traced_cpu_run_reports_every_thread():
    import test_bench_harness as th
    plain = trace_reduce.reduce_trace
    line, rep = sr.traced_report(th.small("store-audit"), seed=2**31 + 98,
                                 seconds=0.4)
    assert trace_reduce.reduce_trace is plain
    assert line["correct"], line["checks"]
    assert rep["mixed_lines"] == []
    # tid 0 audits, tids 1-3 transfer
    assert set(rep["by_tid"]) == {"0", "1", "2", "3"}
    assert "mvstore.read_bulk" in rep["by_tid"]["0"]["span_s"]
    assert "mvstore.read" in rep["by_tid"]["1"]["span_s"]
    assert rep["read_bulk_words"]["all"] >= th.ACCOUNTS
    assert all(" | " in g for g, _ in rep["idle_gaps"])
    assert rep["clock"]["programs"] == 0       # no TPU plane on the CPU
