"""Request kinds: a kind the harness has never seen enters as files and
decides ``correct`` by its own reference, and every traffic file names a
kind that loads."""
import time
from pathlib import Path

import pytest

from bench import gen, harness, reference

FAKES = Path(__file__).parent / "kinds"
SEED = 2**32 + 91


def _run(config, traffic, seconds):
    spec = harness.benchmark()
    cell = {"workload": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": traffic, "end_to_end": spec["end_to_end"],
            "per_layer": []}
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            harness.peaks("TPU v5 lite"))


# ----------------------------------------------------------------------
# a kind that enters as files
# ----------------------------------------------------------------------

FAKE_TRAFFIC = {"kind": "fake", "distinct_queries": 40, "pool_seed": 1,
                "submitters": 1, "rate_qps": 100}


@pytest.mark.parametrize("skew, correct", [(0, True), (1, False)])
def test_a_kind_the_harness_has_never_seen_is_found_by_name(monkeypatch,
                                                            skew, correct):
    monkeypatch.setattr(harness, "KINDS", FAKES)
    res = _run({"size": 64, "engine": {"skew": skew}}, FAKE_TRAFFIC, 0.3)
    assert res["correct"] is correct and res["attempted"] == 30
    assert res["compared"]["mismatched"]["value"] == (0 if correct else 30)
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.benchmark()["end_to_end"]}


def test_a_new_kinds_control_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "KINDS", FAKES)
    kind = harness.kind_of(FAKE_TRAFFIC)
    table = kind.data({"size": 64}, SEED)
    lg = gen.build_log(FAKE_TRAFFIC, kind.pool(FAKE_TRAFFIC, table), 100,
                       0.3, SEED)
    counts, ok = harness.compare_log(
        kind, table, lg, [kind.control(table, lg.pool[i]) for i in lg.which])
    assert not reference.verdict(counts) and not ok.any()


@pytest.mark.parametrize("path", sorted((harness.BENCH / "traffic").glob(
    "*.json")), ids=lambda p: p.stem)
def test_every_traffic_file_names_a_kind_that_loads(path):
    import json

    traffic = json.loads(path.read_text())
    kind = harness.kind_of(traffic)
    for name in ("data", "engine", "pool", "warm", "submit", "answer",
                 "route", "reference", "control"):
        assert callable(getattr(kind, name)), name


def test_an_unknown_kind_is_refused():
    with pytest.raises(SystemExit, match="no request kind 'nonesuch'"):
        harness.kind_of({"kind": "nonesuch"})
