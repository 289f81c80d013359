"""The harness end to end at test size on the CPU: it finds a cell's parts
by name, a sound run is correct, and the comparison fails for the
control (a lower precision) and for a token altered where the engine
produces it.  The chip check is the only part skipped: `serve_cell` is
what `main` runs once it has found the chip."""
import json
import types

import numpy as np
import pytest

import tiny_cell
from bench import run
from bench.lib import catalog


class _Device:
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


def _run(base, capsys, seed=2 ** 33 + 5, control=False, trace=0):
    cat = catalog.Catalog(base)
    cell = cat.workload("tiny-cell")
    args = types.SimpleNamespace(workload="tiny-cell", seed=seed,
                                 seconds=2.0, trace=trace, control=control,
                                 rates=None)
    assert run.serve_cell(args, cat, cell, cat.config(cell["config"]),
                          cat.traffic(cell["traffic"]), _Device(), 1) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_parts_are_found_by_name(tmp_path):
    base = tiny_cell.make(tmp_path)
    # a later cell: new files and entries, no file edited
    cfg = json.loads((base / "bench/configs/tiny.json").read_text())
    (base / "bench/configs/other.json").write_text(
        json.dumps(dict(cfg, name="other")))
    (base / "bench/traffic/bursty.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 3.0}))
    (base / "bench/metrics/queue_wait.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (base / "bench/families/ssm.py").write_text("FAMILY = 'ssm'\n")
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "other", "source": "test",
                             "file": "bench/configs/other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other-bursty", "config": "other",
                               "traffic": "bursty", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "queue_wait", "unit": "ms",
                               "better": "lower", "source":
                               "program_counter", "layer": "engine",
                               "moves": "tok_s",
                               "workloads": ["other-bursty"]})
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    cat = catalog.Catalog(base)
    cell = cat.workload("other-bursty")
    assert cat.config(cell["config"])["name"] == "other"
    assert cat.traffic(cell["traffic"])["rate_per_s"] == 3.0
    names = [m["name"] for m in cat.metrics("other-bursty", "per_layer")]
    assert "queue_wait" in names
    assert "queue_wait" not in [m["name"] for m in
                                cat.metrics("tiny-cell", "per_layer")]
    assert cat.reader("queue_wait")(None) == 42.0
    assert cat.family("ssm").FAMILY == "ssm"
    assert hasattr(cat.family("dense"), "served_gaps")
    with pytest.raises(KeyError):
        cat.workload("missing")


def test_sound_run_is_correct(tmp_path, capsys):
    res, err = _run(tiny_cell.make(tmp_path), capsys)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tok_s", "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "compared"
    assert "programs built inside the window: 0 " in err
    assert err.strip().splitlines()[-2].startswith("compared: max_logit_gap")


@pytest.mark.parametrize("fmt,control", [
    ("w8a8", {"kind": "program_format",
              "weights": {"format": "w4a8", "weight_bits": 4}}),
    ("w4a8", {"kind": "reference_precision", "act_bits": 4}),
], ids=["program-w4", "reference-a4"])
def test_control_is_not_correct(tmp_path, capsys, fmt, control):
    base = tiny_cell.make(tmp_path, fmt=fmt)
    path = base / "bench/configs/tiny.json"
    cfg = json.loads(path.read_text())
    cfg["correct"]["control"] = control
    path.write_text(json.dumps(cfg))
    res, _ = _run(base, capsys, control=True)
    assert res["correct"] is False
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_token_is_not_correct(tmp_path, capsys, monkeypatch):
    """A token altered where the decode segment produces it."""
    from repro.launch.engine import ServeEngine
    harvest = ServeEngine._harvest
    seen = {"n": 0}

    def altered(self, seq, bad, now):
        seen["n"] += 1
        if seen["n"] % 3 == 0:
            seq = np.array(seq)
            seq[-1] = (seq[-1] + 1) % self.cfg.vocab
        return harvest(self, seq, bad, now)

    monkeypatch.setattr(ServeEngine, "_harvest", altered)
    res, _ = _run(tiny_cell.make(tmp_path), capsys)
    assert res["correct"] is False


def test_closed_loop_traced_run(tmp_path, capsys):
    res, err = _run(tiny_cell.make(tmp_path, fmt="w4a8", loop="closed"),
                    capsys, trace=1)
    assert res["correct"] is True
    assert {"hidden_host_share", "decode_occupancy", "ttft_p95_ms.engine",
            "stall_p95_ms.engine"} <= set(res["metrics"])
    # no device plane in a CPU trace: the step's shares find nothing to
    # read and are left out, never reported as 0
    assert res["device"]["busy_s"] == 0
    assert not {"step_mfu", "step_mbu", "device_idle_share"} & set(
        res["metrics"])
    assert 0 < res["metrics"]["decode_occupancy"]["value"] <= 100
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_shared_prefix_mix_with_a_prefix_cache(tmp_path, capsys):
    """A later cell's parts, as data only: a mix of shared prefixes with
    bursts, and engine keys (a prefix cache, chunked prefill) that go to
    the engine as they stand in the configuration."""
    base = tiny_cell.make(tmp_path)
    path = base / "bench/configs/tiny.json"
    cfg = json.loads(path.read_text())
    cfg["engine"].update(prefix_cache=64, prefill_chunk=16)
    path.write_text(json.dumps(cfg))
    mix = {"loop": "open", "order_seed": 1, "rate_per_s": 8.0,
           "drain_s": 60, "prefix": {"count": 3, "len": 32, "zipf": 1.2},
           "bursts": {"period_s": 1.0, "on_s": 0.5},
           "prompt": {"dist": "uniform", "min": 4, "max": 20},
           "output": {"dist": "uniform", "min": 2, "max": 8}}
    (base / "bench/traffic/tiny-mix.json").write_text(json.dumps(mix))
    res, err = _run(base, capsys, trace=0)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    line = [x for x in err.splitlines() if x.startswith("engine: ")][-1]
    assert json.loads(line[len("engine: "):])["prefix_cache"][
        "tokens_skipped"] > 0
