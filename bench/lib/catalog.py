"""Find a cell's parts by name.

`BENCHMARK.json` names each cell's configuration and traffic mix and each
metric; the parts live in files of their own, found here by that name:

    bench/configs/<config>.json     sizes, weight format, engine settings
    bench/traffic/<traffic>.json    a mix's parameters (lib/traffic.py)
    bench/metrics/<metric>.py       `read(ctx) -> float | None`
    bench/families/<family>.py      a model family's weights, reference
                                    and counts (families/dense.py)

so a later cell adds files and entries and edits none.  `base` is the
directory that holds `BENCHMARK.json` and `bench/`.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

#: keys of a dense configuration file that the model code, the reference
#: and the counts read
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_hidden_layers", "num_key_value_heads", "vocab_size",
              "rope_theta", "rms_norm_eps", "tie_word_embeddings",
              "attention_bias")


class Catalog:
    def __init__(self, base):
        self.base = pathlib.Path(base)
        self.bench = json.loads((self.base / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return json.loads((self.base / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.base / "bench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """The `read(ctx)` function of bench/metrics/<metric>.py."""
        return self._load("metrics", metric).read

    def family(self, name: str):
        """The module bench/families/<name>.py."""
        return self._load("families", name)

    def _load(self, kind: str, name: str):
        path = self.base / "bench" / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def model_block(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS if k in cfg}
