"""Whole runs of a tiny cell on the CPU, past the harness's look for a
chip: a sound run is correct, the control and each fault the cell can
have are not; and ``run.py`` itself refuses to run without a TPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, reference

ROOT = Path(__file__).resolve().parents[2]
CONFIG = {"name": "tiny", "documents": 20000, "vocabulary": 30000,
          "zipf_alpha": 1.2294, "tokens_per_doc": 72.55,
          "posting_band": [16, 800],
          "engine": {"w": 256, "m": 2, "deadline_us": 2000, "flush_tier": 2,
                     "max_inflight": 8, "result_cache": 0,
                     "hashbin_ratio": 100}}
TRAFFIC = {"k_mix": [[2, 0.68], [3, 0.23], [4, 0.09]], "min_postings": 513,
           "distinct_queries": 120, "pool_seed": 7, "submitters": 1,
           "rate_qps": 150}
SEED = 2**32 + 77


@pytest.fixture(autouse=True)
def _few_tiers(monkeypatch):
    """Warm batch tiers up to 4 only: the CPU compiles slowly."""
    monkeypatch.setattr(harness, "WARM_TIER_MAX", 4)


def _cell(traced=False):
    spec = harness.benchmark()
    return {"workload": {"name": "tiny", "chips": 1}, "config": CONFIG,
            "traffic": TRAFFIC,
            "end_to_end": spec["end_to_end"],
            "per_layer": [m for m in spec["per_layer"]
                          if m["name"].endswith(".lat")]}


def _run(traced=False):
    peak = harness.peaks("TPU v5 lite")
    return harness.run_cell(_cell(traced), SEED, 0.6, traced,
                            time.perf_counter(), peak)


def test_sound_run_is_correct_and_prints_the_contract_line():
    res = _run()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 90
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.benchmark()["end_to_end"]}
    assert list(res)[-1] == "compared"
    assert res["compared"]["mismatched"] == {"value": 0, "limit": 0}
    json.dumps(res)


def test_traced_run_reads_the_span_metrics():
    res = _run(traced=True)
    assert res["correct"]
    assert {"plan_us.lat", "queue_wait_ms.lat",
            "batch_fill.lat"} <= set(res["metrics"])
    assert res["metrics"]["batch_fill.lat"]["value"] >= 1
    assert "window_s" in res["device"] and "breakdown" in res


def _fault(monkeypatch, alter):
    """Break the timed path where answers are produced: every collected
    bucket's answers pass through ``alter`` first."""
    from repro.exec import batch

    collect = batch.InFlightBucket.collect
    monkeypatch.setattr(batch.InFlightBucket, "collect",
                        lambda self: alter(collect(self)))


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    def add_one(out):
        qi = min(out)
        values, stats = out[qi]
        extra = np.uint32(values[-1] + 1 if len(values) else 1)
        out[qi] = (np.append(values, extra), stats)
        return out

    _fault(monkeypatch, add_one)
    res = _run()
    assert not res["correct"] and res["failed"] > 0
    assert res["compared"]["mismatched"]["value"] > 0


def test_half_of_each_bucket_left_out_fails(monkeypatch):
    def half(out):
        for qi in sorted(out)[len(out) // 2:]:
            out[qi] = (np.empty(0, np.uint32), out[qi][1])
        return out

    _fault(monkeypatch, half)
    res = _run()
    assert not res["correct"]


def test_the_control_is_not_correct():
    from repro.core.engine import EXEC_COUNTERS

    counts = harness.CompileCount(EXEC_COUNTERS)
    served = harness.build(CONFIG, TRAFFIC, SEED, False, counts)
    win = harness.serve_window(served, TRAFFIC, 150, 0.6, SEED)
    sound, _ = harness.check(served, win)
    queries = [win.log.pool[i] for i in win.log.which]
    ctl, ok = harness.compare_log(
        served.kind, served.data, win.log,
        [served.kind.control(served.data, q) for q in queries])
    assert reference.verdict(sound) and not reference.verdict(ctl)
    assert ctl["mismatched"] > 0 and not ok.all()


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "gov2-conj", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_every_cell_resolves_its_files(name):
    c = harness.cell(name)
    assert c["traffic"]["rate_qps"] > 0
    assert any(m["name"] == "setup_s" for m in c["end_to_end"])
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["per_layer"]:
        assert callable(harness.reader(m["name"]))
