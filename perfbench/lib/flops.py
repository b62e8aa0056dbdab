"""Operations and bytes from shapes. Model FLOPs: what the forward (and for
training the backward) pass requires, a multiply-add counted as 2, causal
attention counted once (the half of the score matrix that is used), no
recomputation. ``cfg`` is a configuration file's dict (Hugging Face keys).
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "hd": hd, "kv": cfg["num_key_value_heads"],
            "dff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    layers' projections and the (tied) output head. The embedding lookup
    is a gather and costs no FLOPs."""
    s = dims(cfg)
    attn = s["d"] * s["h"] * s["hd"] * 2 + s["d"] * s["kv"] * s["hd"] * 2
    ffn = 3 * s["d"] * s["dff"]
    return s["L"] * (attn + ffn) + s["V"] * s["d"]


def n_params(cfg: dict) -> int:
    """Parameters held (tied head: the embedding counted once; norms in)."""
    s = dims(cfg)
    return matmul_params(cfg) + s["L"] * 2 * s["d"] + s["d"]


def attn_flops_fwd(cfg: dict, seq: int, causal: bool = True) -> float:
    """QK^T and PV of ONE sequence over all layers, forward."""
    s = dims(cfg)
    full = 2 * 2 * seq * seq * s["h"] * s["hd"]
    return s["L"] * (full / 2 if causal else full)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3x forward) per token at sequence ``seq``."""
    fwd = 2 * matmul_params(cfg) + attn_flops_fwd(cfg, seq) / seq
    return 3 * fwd


def forward_flops(cfg: dict, new_tokens: int, context: int) -> float:
    """Forward FLOPs of ``new_tokens`` real tokens whose last one sees
    ``context`` keys (a prompt: new == context, causal; one decoded token:
    new == 1)."""
    s = dims(cfg)
    first = context - new_tokens + 1
    keys = (first + context) * new_tokens / 2     # sum of keys each query sees
    attn = s["L"] * 2 * 2 * keys * s["h"] * s["hd"]
    return 2 * matmul_params(cfg) * new_tokens + attn


def flash_train_min_seconds(cfg: dict, seq: int, n_seq: int, peaks: dict,
                            dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for causal attention forward +
    backward over ``n_seq`` sequences in every layer. FLOPs: forward QK^T
    and PV (2 products), backward dQ, dK, dV, dP and the recomputed scores
    (5 products), causal half. Bytes: q, k, v read and o written forward;
    q, k, v, o, do read and dq, dk, dv written backward (k and v at their
    grouped width)."""
    s = dims(cfg)
    one = 2 * seq * seq * s["h"] * s["hd"] / 2
    flops = s["L"] * n_seq * 7 * one
    qo = seq * s["h"] * s["hd"] * dtype_bytes
    kv = seq * s["kv"] * s["hd"] * dtype_bytes
    fwd_bytes = 2 * qo + 2 * kv
    bwd_bytes = 3 * qo + 2 * kv + qo + 2 * kv
    byts = s["L"] * n_seq * (fwd_bytes + bwd_bytes)
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "seconds": max(t_f, t_b),
            "bound": "flops" if t_f >= t_b else "bytes"}
