"""Operations and bytes of the ``LongCat-Flash`` configuration from shapes.
Model FLOPs: what the forward pass requires of THIS chip's share, a
multiply-add counted as 2, every real prompt token and every decoded token
once: the non-expert products (both attention sublayers' projections in
the EXPANDED form at the published widths, both dense FFNs, the router),
the routed experts at the EXPECTED number of pairs that land on the
experts held here (``moe_topk`` x held / router width a token a layer:
0.25 at 16 of 768 under top-12), the identity experts 0, attention over
the keys a query sees at the published 192 / 128 widths, the head over the
rows held. No padding, no dead lanes, no absorbed products. ``cfg`` is the
configuration file's dict.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["ffn_hidden_size"],
            "Fe": cfg["expert_ffn_hidden_size"],
            "E": cfg["n_routed_experts"],
            "routed": cfg["n_routed_experts_routed"] + cfg["zero_expert_num"],
            "k": cfg["moe_topk"], "V": cfg["vocab_size"],
            "L": cfg["num_layers"]}


def sublayer_params(cfg: dict) -> dict:
    """Matrix parameters of ONE attention sublayer and ONE dense FFN."""
    s = dims(cfg)
    mla = s["d"] * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"]) \
        + s["d"] * (s["rkv"] + s["dr"]) \
        + s["rkv"] * s["H"] * (s["dn"] + s["dv"]) \
        + s["H"] * s["dv"] * s["d"]
    return {"mla": mla, "ffn": 3 * s["d"] * s["F"],
            "expert": 3 * s["d"] * s["Fe"],
            "router": s["d"] * s["routed"]}


def matmul_params_per_token(cfg: dict) -> float:
    """Weights that take part in a product for one token, all layers and
    the untied head (the embedding is a gather)."""
    s, p = dims(cfg), sublayer_params(cfg)
    pairs = s["k"] * s["E"] / s["routed"]
    layer = 2 * (p["mla"] + p["ffn"]) + p["router"] + pairs * p["expert"]
    return s["L"] * layer + s["d"] * s["V"]


def n_params(cfg: dict) -> int:
    """Parameters held here: both sublayers' matrices and four gains, the
    router and its selection bias, the experts held, embedding, untied
    head and the final gain."""
    s, p = dims(cfg), sublayer_params(cfg)
    gains = 2 * s["d"] + s["rq"] + s["rkv"]
    layer = 2 * (p["mla"] + p["ffn"] + gains) + p["router"] + s["routed"] \
        + s["E"] * p["expert"]
    return s["L"] * layer + 2 * s["V"] * s["d"] + s["d"]


def keys_seen(first: int, last: int) -> float:
    """Sum over the queries at positions first..last (0-based) of the keys
    each sees: p + 1."""
    upto = lambda p: (p + 1) * (p + 2) / 2
    return upto(last) - upto(first - 1)


def forward_flops(cfg: dict, new_tokens: int, context: int) -> float:
    """Forward FLOPs of ``new_tokens`` real tokens whose last one sees a
    context of ``context`` tokens (a prompt: new == context; one decoded
    token: new == 1). Attention: two sublayers a layer, QK^T over 192 and
    PV over 128 a head."""
    s = dims(cfg)
    keys = 2 * s["L"] * keys_seen(context - new_tokens, context - 1)
    attn = 2 * keys * s["H"] * (s["dn"] + s["dr"] + s["dv"])
    return 2 * matmul_params_per_token(cfg) * new_tokens + attn


def latent_read_min_seconds(cfg: dict, pages: float, page_tokens: int,
                            peaks: dict, dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for ONE call of the latent read (one
    attention sublayer, one token step) over ``pages`` mapped pages: each
    page's rows ONCE at the PUBLISHED ``kv_lora_rank + qk_rope_head_dim``
    values (dead lanes, a second copy or a padded width then read as
    distance from the roofline, not as work), against the products the
    query heads owe those keys in the absorbed form (a score over the
    row's 576 and a value sum over its 512, a head a key). About 121
    FLOP/B: under a v5e's ~240, so the bytes bound it."""
    s = dims(cfg)
    row = s["rkv"] + s["dr"]
    byts = pages * page_tokens * row * dtype_bytes
    flops = pages * page_tokens * 2 * s["H"] * (row + s["rkv"])
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "seconds": max(t_f, t_b),
            "bound": "flops" if t_f >= t_b else "bytes"}


def flash_prefill_min_seconds(cfg: dict, seq: int, peaks: dict,
                              dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for ONE padded prompt's attention,
    forward, over all attention sublayers (two a layer), at the PUBLISHED
    widths: ``seq`` queries a sublayer (the padding is attended too),
    QK^T over ``qk_nope + qk_rope`` = 192 and PV over ``v_head_dim`` = 128
    a head over the keys each query sees. Bytes: q and k read at 192 a
    head, v read and o written at 128, once a sublayer. The padding of all
    three to 256 lanes that the call makes today then reads as distance
    from the roofline, not as work."""
    s = dims(cfg)
    subs = 2 * s["L"]
    flops = subs * 2 * keys_seen(0, seq - 1) * s["H"] \
        * (s["dn"] + s["dr"] + s["dv"])
    byts = subs * dtype_bytes * seq * s["H"] \
        * (2 * (s["dn"] + s["dr"]) + 2 * s["dv"])
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": byts, "seconds": max(t_f, t_b),
            "bound": "flops" if t_f >= t_b else "bytes"}
