"""Operations and bytes that the algorithm needs, from shapes alone.

`m` is the model block of a configuration file (published key names);
`fmt` its weight format block: `weight_bits`, `act_bits` and the names of
the `quantized` projections.  Nothing here reads the program: these are
the yardstick's own counts, so a change to the program cannot change
them.  Padding, recomputation and bucket slack are not counted -- they
are not work the algorithm needs.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def dims(m: dict) -> dict:
    d = m["hidden_size"]
    hd = d // m["num_attention_heads"]
    return {"d": d, "f": m["intermediate_size"], "v": m["vocab_size"],
            "hd": hd, "nh": m["num_attention_heads"],
            "qd": m["num_attention_heads"] * hd,
            "kvd": m["num_key_value_heads"] * hd,
            "layers": m["num_hidden_layers"]}


def gemms(m: dict) -> Dict[str, Tuple[int, int]]:
    """(K, N) of every projection of one layer, and of the head."""
    x = dims(m)
    return {"wq": (x["d"], x["qd"]), "wk": (x["d"], x["kvd"]),
            "wv": (x["d"], x["kvd"]), "wo": (x["qd"], x["d"]),
            "wg": (x["d"], x["f"]), "wi": (x["d"], x["f"]),
            "wo_mlp": (x["f"], x["d"]), "lm_head": (x["d"], x["v"])}


def weight_bytes(m: dict, fmt: dict) -> float:
    """Bytes of every matrix one forward step reads once: quantized
    weights at `weight_bits` plus float32 scales per output column, the
    rest bf16 (the tied head reads the bf16 embedding)."""
    x = dims(m)
    total = 0.0
    for name, (k, n) in gemms(m).items():
        times = 1 if name == "lm_head" else x["layers"]
        if name in fmt["quantized"]:
            total += times * (k * n * fmt["weight_bits"] / 8 + 4 * n)
        else:
            total += times * k * n * BF16
    return total


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of one position in every layer."""
    x = dims(m)
    return 2 * x["layers"] * x["kvd"] * BF16


def token_ops(m: dict, fmt: dict) -> Dict[str, float]:
    """Operations of one token through every projection and the head,
    split by the precision they run in: {"int8": ..., "bf16": ...}."""
    x = dims(m)
    out = {"int8": 0.0, "bf16": 0.0}
    for name, (k, n) in gemms(m).items():
        times = 1 if name == "lm_head" else x["layers"]
        out["int8" if name in fmt["quantized"] else "bf16"] += \
            times * 2 * k * n
    return out


def attention_ops(m: dict, context: float) -> float:
    """bf16 operations of one query over `context` keys in every layer
    (scores and the weighted sum of values)."""
    x = dims(m)
    return x["layers"] * 4 * x["nh"] * x["hd"] * context


def prefill_context(prompt_len: int) -> float:
    """Sum of the keys each prompt position attends to (causal)."""
    return prompt_len * (prompt_len + 1) / 2


def roofline_s(ops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least time on the chip of an int8 GEMM call, and which bound sets
    it."""
    t_ops, t_mem = ops / peak["int8_ops_s"], nbytes / peak["hbm_bytes_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
