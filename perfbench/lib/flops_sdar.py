"""Operations and bytes of the ``sdar_moe`` configuration from shapes.
Model FLOPs: what GENERATING requires, a multiply-add counted as 2: every
real prompt token once and every emitted token once, each through the
projections, the router, its ``num_experts_per_tok`` experts and the head,
attention over the keys its block sees (everything up to its block's
end). The four further forwards a block takes (three denoising steps that
keep one position each, the commit) are overhead of the method, not work
of the model: a share of the peak counted this way bounds what a later
change can claim. No padding, no recomputation. ``cfg`` is the
configuration file's dict.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "dff": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "B": cfg["block_length"]}


def matmul_params_per_token(cfg: dict) -> float:
    """Weights that take part in a product for one token, all layers and
    the untied head (the embedding is a gather)."""
    s = dims(cfg)
    attn = 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]
    layer = attn + s["d"] * s["E"] + s["k"] * 3 * s["d"] * s["dff"]
    return s["L"] * layer + s["V"] * s["d"]


def n_params(cfg: dict) -> int:
    """Parameters held here: every expert, both norms' gains and the two
    head gains a layer, embedding, head and final gain."""
    s = dims(cfg)
    attn = 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"]
    layer = attn + s["d"] * s["E"] + s["E"] * 3 * s["d"] * s["dff"] \
        + 2 * s["d"] + 2 * s["hd"]
    return s["L"] * layer + 2 * s["V"] * s["d"] + s["d"]


def keys_seen(first: int, last: int, block: int) -> float:
    """Sum over the queries at positions first..last (0-based) of the keys
    each sees under the block-causal mask: all up to its block's end."""
    return float(sum((p // block + 1) * block for p in range(first,
                                                             last + 1)))


def forward_flops(cfg: dict, new_tokens: int, context: int) -> float:
    """FLOPs of ``new_tokens`` real tokens, each counted once, the last at
    position ``context - 1``."""
    s = dims(cfg)
    keys = s["L"] * keys_seen(context - new_tokens, context - 1, s["B"])
    return 2 * matmul_params_per_token(cfg) * new_tokens \
        + 2 * 2 * keys * s["h"] * s["hd"]


def paged_read_min_seconds(cfg: dict, pages: float, page_tokens: int,
                           peaks: dict, dtype_bytes: int = 2) -> float:
    """Least time the chip could take for ONE call of the paged read (one
    layer, one forward) over ``pages`` mapped pages: K and V of every page
    read once, all kv heads; against it the products a block's rows owe
    those keys. Bound by the bytes on a v5e (131,072 B a page against 4.2
    MFLOP)."""
    s = dims(cfg)
    byts = pages * 2 * s["kv"] * page_tokens * s["hd"] * dtype_bytes
    flops = pages * 2 * 2 * s["B"] * s["h"] * s["hd"] * page_tokens
    return max(byts / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
