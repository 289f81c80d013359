"""A benchmark checkout at test size: BENCHMARK.json with one cell, a
small dense configuration (quantized without the production size
floors, served on the `ref` lowering), a short open-loop mix, and the
real metric readers."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

MODEL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_hidden_layers": 2,
         "num_key_value_heads": 2, "vocab_size": 256, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
         "attention_bias": True}


def make(base, fmt="w8a8", loop="open", limit=0.05):
    base = pathlib.Path(base)
    (base / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (base / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for part in ("metrics", "families"):
        shutil.copytree(ROOT / "bench" / part, base / "bench" / part,
                        dirs_exist_ok=True)
    cfg = dict(MODEL, name="tiny", reduced=[],
               program={"arch": "qwen1.5-0.5b", "family": "dense",
                        "overrides": {"attn_q_chunk": 32}},
               weights={"format": fmt, "weight_bits": 4 if fmt == "w4a8"
                        else 8, "act_bits": 8, "force": True,
                        "quantized": ["wq", "wk", "wv", "wo", "wg", "wi",
                                      "wo_mlp"]},
               engine={"n_slots": 4, "max_cache_len": 128, "segment_len": 4,
                       "min_batch_bucket": 4, "min_len_bucket": 64,
                       "silvia_passes": "off", "lowering": "ref"},
               correct={"sample": 3, "max_logit_gap": limit,
                        "control": {"kind": "reference_precision",
                                    "act_bits": 4}})
    (base / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    if loop == "open":
        mix = {"loop": "open", "rate_per_s": 8.0, "drain_s": 60,
               "order_seed": 0,
               "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                          "min": 8, "max": 60},
               "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 2, "max": 24}}
    else:
        mix = {"loop": "closed", "clients_per_slot": 2, "drain_s": 60,
               "order_seed": 0,
               "prompt": {"dist": "uniform", "min": 8, "max": 40},
               "output": {"dist": "uniform", "min": 4, "max": 16}}
    (base / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test size"}]
    bench["workloads"] = [{"name": "tiny-cell", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1,
                           "why": "test size"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    return base
