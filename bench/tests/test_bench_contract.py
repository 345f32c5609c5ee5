"""BENCHMARK.json and the files it names hold together: every cell finds
its configuration, traffic and limits, every per-layer metric its
reader, every configuration a family that exposes the whole contract,
and a run with no chip prints no result."""
import json
import os
import re
import subprocess
import sys

import pytest

import cells

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = cells.config(c["name"])
        assert os.path.join("bench", "configs", c["name"] + ".json") == \
            c["file"]
        assert cfg["source"] == c["source"] and cfg["reduced"] == \
            c["reduced"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cells.config(w["config"])
        t = cells.traffic(w["traffic"])
        cells.generator(t["generator"])
        lim = cells.limits(w["name"])
        assert lim["widest_gap"]["limit"] > 0


FAMILY_CONTRACT = ("dims", "all_leaves", "layer_leaves", "global_leaves",
                   "model_config", "program_params", "decode_work")
TESTS = os.path.join(cells.HERE, "tests")


@pytest.mark.parametrize("where", [c["file"] for c in BENCH["configs"]] + [
    "bench/tests/tiny_config.json", "bench/tests/tiny_moe_config.json"])
def test_every_configuration_names_a_family_with_the_whole_contract(
        where, monkeypatch):
    if where.endswith("tiny_moe_config.json"):
        monkeypatch.setattr(cells, "FAMILIES",
                            os.path.join(TESTS, "fixture_families"))
        monkeypatch.setattr(cells, "REFERENCES",
                            os.path.join(TESTS, "fixture_references"))
    with open(os.path.join(cells.ROOT, where)) as f:
        fam = cells.family(json.load(f))
    for name in FAMILY_CONTRACT:
        assert callable(getattr(fam, name)), name
    ref = cells.reference(fam)
    assert callable(ref.served_gap) and callable(ref.control_gap)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in cells.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cells.per_layer(w["name"])


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert callable(cells.metric_reader(m["name"]).read)
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])


def test_bounds_and_run_length_are_within_the_contract():
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_no_chip_no_result():
    root = os.path.dirname(cells.HERE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        cells.workload("ghost")
