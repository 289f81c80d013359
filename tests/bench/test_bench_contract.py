"""BENCHMARK.json and the files it names hold together: every name
follows the rules, every cell's configuration, mix and metric readers
exist, and every configuration states the keys the harness reads."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].split("/")[0] in BENCH["paths"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


def test_names_units_and_uniqueness():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    assert len(names) == len(set(names))


def test_every_cell_has_its_parts():
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg = json.loads((ROOT / cfgs[w["config"]]["file"]).read_text())
        assert cfg["name"] == w["config"]
        for key in ("weights", "engine", "correct", "program"):
            assert key in cfg
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        family = cfg["program"]["family"]
        assert (ROOT / "bench" / "families" / f"{family}.py").exists()
        used.add(w["config"])
        e2e = [m for m in BENCH["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        per = [m for m in BENCH["per_layer"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert per
    assert used == set(cfgs)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_bounds(m):
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


def test_configs_are_at_published_width():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"] == []
        assert cfg["source"] == c["source"]
