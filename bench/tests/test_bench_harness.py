"""The harness end to end on the CPU at a tiny size: the cells against
the system, the control and planted faults against the reference, the
refusal of a machine without a TPU, and cells found by name."""
import json
import os
import shutil
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run as bench_run  # noqa: E402

ACCOUNTS = 20_000


def small(name, root=ROOT):
    """The cell ``name`` at a size a test run can hold: the same traffic
    shape over 20,000 accounts, warm-up cut to one operation."""
    cell = bench_run.load_cell(name, root)
    cell.config["bank"]["accounts"] = ACCOUNTS
    cell.config["load_chunk"] = 4096
    t = cell.traffic
    if t.audit_chunk:
        t.audit_chunk = 4096
    t.warmup_seconds = 0.0
    t.warmup_audits = min(t.warmup_audits, 1)
    t.warmup_transfers = min(t.warmup_transfers, 10)
    return cell


def drive(cell, make_system=None, seconds=0.4, trace=False):
    return bench_run.run_cell(cell, seed=2**31 + 99, seconds=seconds,
                              trace=trace, make_system=make_system)


def reference_system(write_behind):
    def make(config, balances, n_threads):
        return reference.ReferenceBank(balances, write_behind=write_behind)
    return make


def test_cell_is_correct_on_the_system():
    line = drive(small("store-audit"))
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert m["transfers_per_s"]["value"] > 0
    assert m["transfer_ms_p95"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    assert m["audit_words_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"


def test_traced_cell_reports_its_layer_metrics():
    line = drive(small("store-audit"), trace=True)
    assert line["correct"]
    got = set(line["metrics"])
    # the CPU has no device plane: the device readers find nothing
    assert {"transfer_attempts_per_commit", "audit_attempts_per_commit",
            "commit_ms_p50.store"} <= got
    assert "gather_read_roofline" not in got
    assert line["device"]["window_s"] > 0
    assert len(line["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("write_behind", [False, True])
def test_reference_in_the_systems_place(write_behind):
    line = drive(small("store-audit"), reference_system(write_behind))
    assert line["correct"] is not write_behind
    if not write_behind:
        return
    # the control acknowledges a transfer before applying it
    assert line["checks"]["final_bad_accounts"]["value"] > 0


# -- faults planted under the timed path ------------------------------------


def _after_load(cell, patch):
    """A system factory that builds the program, then plants a fault."""
    def make(config, balances, n_threads):
        system = cell.kind.ProgramSystem(config, balances, n_threads)
        patch(system)
        return system
    return make


def _store_unchanged(monkeypatch):
    from repro.core import mvstore
    orig = mvstore.mv_commit_fused

    def dropped(state, key, addrs, values, **kw):
        return orig(state, key, np.zeros(0, np.int64),
                    np.zeros(0, np.int64), **kw)
    return lambda s: monkeypatch.setattr(mvstore, "mv_commit_fused",
                                         dropped)


def _gather_fault(monkeypatch, kind):
    from repro.core.engine import bulkread

    def bad(vals):
        vals = np.array(vals, copy=True)
        if kind == "half":                 # second half never read
            vals[vals.shape[0] // 2:] = vals[0]
        else:                              # one answer altered
            vals[vals.shape[0] // 3] += 1
        return vals

    orig = bulkread.gather_row

    def gather_row(row, addrs):
        got = orig(row, addrs)
        return bad(got) if addrs.size >= 4096 else got
    return lambda s: monkeypatch.setattr(bulkread, "gather_row", gather_row)


def _versioned_audits(monkeypatch):
    """Every read-only transaction of the traffic threads takes the
    store's versioned path, served from the version ring (sound: the
    chip reaches it after aborts, a short CPU run seldom does)."""
    from repro.api.mvhandle import MVStoreHandle
    orig = MVStoreHandle.begin

    def begin(self, tid=0):
        if threading.current_thread().name.startswith("bench-"):
            self._readers[tid].versioned = True
        return orig(self, tid)
    monkeypatch.setattr(MVStoreHandle, "begin", begin)


def _stale_ring_slot(monkeypatch, system):
    """Versioned reads served one commit late: from the ring slot just
    below the newest one the reader's clock allows, once the accounts
    are loaded.  Each such snapshot is consistent in itself and only
    misses the latest transfer."""
    from repro.api import mvhandle
    _versioned_audits(monkeypatch)
    loaded = system.tm._snap[0]            # the clock after the load
    newest = mvhandle._ring_slot

    def one_late(ring_ts, read_clock):
        slot = newest(ring_ts, read_clock)
        if slot is None or ring_ts[slot] <= loaded:
            return slot
        return newest(ring_ts, int(ring_ts[slot]) - 1)
    monkeypatch.setattr(mvhandle, "_ring_slot", one_late)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "stale"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    if fault == "unchanged":
        patch = _store_unchanged(monkeypatch)
    elif fault == "stale":
        patch = lambda s: _stale_ring_slot(monkeypatch, s)  # noqa: E731
    else:
        patch = _gather_fault(monkeypatch, fault)
    cell = small("store-audit")
    line = drive(cell, _after_load(cell, patch))
    assert not line["correct"], line["checks"]
    if fault == "stale":      # consistent, only late: the audits catch it
        assert line["checks"]["final_bad_accounts"]["value"] == 0
        assert line["checks"]["audit_bad_accounts"]["value"] > 0


def test_versioned_audits_are_correct(monkeypatch, capfd):
    cell = small("store-audit")
    line = drive(cell, _after_load(
        cell, lambda s: _versioned_audits(monkeypatch)))
    assert line["correct"], line["checks"]
    stats = capfd.readouterr().err.split("system stats ", 1)[1]
    assert json.loads(stats.splitlines()[0])["versioned_commits"] > 0


# -- the command ------------------------------------------------------------


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "store-audit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_machine_without_a_tpu(tmp_path):
    out = _command(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_command_needs_more_than_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": "", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    out = _command(tmp_path, env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    # it stops for want of the program, before it looks for a chip
    assert "No module named 'repro'" in out.stderr
    assert "no TPU" not in out.stderr


# -- found by name ----------------------------------------------------------


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads(
        (BENCH / "configs" / "bank-2m-store.json").read_text())
    conf["name"] = "bank-tiny-tm"
    conf["system"] = {"backend": "multiverse",
                      "options": {"array_heap": True},
                      "params": {"k1": 2, "k2": 3, "k3": 3,
                                 "lock_table_bits": 16}}
    (tmp_path / "bench/configs/bank-tiny-tm.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench/traffic/chunked-audit.json").write_text(json.dumps(
        {"auditors": 1, "transfer_threads": 2, "audit_chunk": 4096,
         "warmup_audits": 1, "warmup_transfers": 5}))
    (tmp_path / "bench/metrics/transfers_seen.py").write_text(
        "def read(rec):\n    return float(rec.transfers['t_end'].size)\n")
    spec["configs"].append({"name": "bank-tiny-tm", "source": "test",
                            "file": "bench/configs/bank-tiny-tm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tm-chunked",
                              "config": "bank-tiny-tm",
                              "traffic": "chunked-audit", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "transfers_seen", "unit": "txn",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "client retry loop",
                              "moves": "transfers_per_s",
                              "workloads": ["tm-chunked"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = small("tm-chunked", tmp_path)
    assert cell.config["system"]["backend"] == "multiverse"
    assert cell.traffic.transfer_threads == 2
    assert cell.traffic.audit_chunk == 4096
    assert [m["name"] for m in cell.per_layer] == ["transfers_seen"]
    line = drive(cell)
    assert line["correct"]
    assert line["metrics"]["transfers_per_s"]["value"] > 0
    line = drive(small("tm-chunked", tmp_path), trace=True)
    assert line["correct"]
    assert line["metrics"]["transfers_seen"]["value"] > 0
    # nothing that was there had to change
    for path in BENCH.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT)
            assert (tmp_path / rel).read_bytes() == path.read_bytes(), rel


COUNTER_KIND = """
import threading
import time
from types import SimpleNamespace


def parse_traffic(params):
    return dict(params)


def control():
    return None


class Workload:
    def __init__(self, config, traffic, *, seed, annotate=None,
                 make_system=None):
        self.step = config["step"] * traffic["scale"]
        self.ticks, self.total = [], 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        while not self._stop.is_set():
            self.total += self.step
            self.ticks.append(time.perf_counter())
            time.sleep(0.001)

    def start(self):
        self._thread.start()

    def wait_warm(self, timeout_s):
        pass

    def progress(self):
        return f"{len(self.ticks)} ticks"

    def stop(self):
        self._stop.set()

    def finish(self, timeout_s):
        self._thread.join(timeout_s)
        return {}

    def free(self):
        pass

    def record(self, t0, t1, stats):
        return SimpleNamespace(attempted=len(self.ticks), failed=0,
                               stats=stats)

    def end_to_end(self, t0, t1):
        n = sum(t0 <= t <= t1 for t in self.ticks)
        return {"transfers_per_s": n / (t1 - t0)}

    def checks(self):
        off = abs(self.total - self.step * len(self.ticks))
        return {"total": {"value": off, "limit": 0}}
"""


def test_new_kind_of_workload_is_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/kinds/counter.py").write_text(COUNTER_KIND)
    (tmp_path / "bench/configs/counter-3.json").write_text(
        json.dumps({"kind": "counter", "step": 3}))
    (tmp_path / "bench/traffic/double.json").write_text(
        json.dumps({"scale": 2}))
    spec["configs"].append({"name": "counter-3", "source": "test",
                            "file": "bench/configs/counter-3.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "count", "config": "counter-3",
                              "traffic": "double", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = bench_run.load_cell("count", tmp_path)
    assert cell.traffic == {"scale": 2}
    line = drive(cell, seconds=0.2)
    assert line["correct"]
    assert line["checks"] == {"total": {"value": 0, "limit": 0}}
    assert line["metrics"]["transfers_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    for path in BENCH.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT)
            assert (tmp_path / rel).read_bytes() == path.read_bytes(), rel
