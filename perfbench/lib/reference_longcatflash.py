"""The plain reference of the ``LongCat-Flash`` configuration: the forward
pass in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision, in the EXPANDED form of latent attention only: every head's k
and v made from the latent. No absorption, no cache, no pages, no kernel,
no grouped products, no batching. It imports nothing of the program and
takes nothing the program made: weights are drawn here from the seed with
the program's draws, one layer's at a time (four layers are 20.7 GB in
float32), rounded once to bfloat16 as the configuration states, and the
same share of experts and of the vocabulary as the program holds.

With d = hidden_size, H heads, r_q / r_kv the two latent ranks, d_n / d_r /
d_v the head's no-rope, rope and value widths, E real experts (of which
``n_routed_experts`` from ``expert_first`` are held here), Z identity
experts, k = moe_topk, s = routed_scaling_factor, ``norm`` an RMSNorm
(every gain is one), a DOUBLE layer is, from the residual stream x::

    a0 = x  + MLA_0(norm(x))
    y0 = norm(a0)
    m  = MoE(y0)                       # the shortcut: computed here, added last
    b0 = a0 + FFN_0(y0)
    a1 = b0 + MLA_1(norm(b0))
    x' = a1 + FFN_1(norm(a1)) + m

    MLA(u), token at position t (causal):
    q      = (norm(u Wq_a) Wq_b) * sqrt(d / r_q)       -> H x [q_n (d_n) | q_r (d_r)]
    [c|kr] = u Wkv_a                                   -> (r_kv | d_r)
    c      = norm(c) * sqrt(d / r_kv)
    q_r, kr rotated at t over the d_r rope dims only, pairs (2i, 2i+1),
            theta; kr is shared by all heads
    k_n,h  = c Wk_b,h;  v_h = c Wv_b,h                 -> H x d_n, H x d_v
    score_h(t, j) = (q_n,h . k_n,h(j) + q_r,h . kr(j)) / sqrt(d_n + d_r)
    out    = concat_h(softmax_j<=t(score_h) v_h) Wo

    MoE(y): p = softmax(y Wr) over E + Z;  T = the k largest of p + b
            (b: the selection bias, in the choice only);  w_e = s p_e,
            not renormalised
    m = sum_{e in T, e held here} w_e (silu(y Wg_e) * (y Wu_e)) Wd_e
        + (sum_{e in T, e >= E} w_e) y
    FFN(y) = (silu(y Wg) * (y Wu)) Wd  at width ffn_hidden_size
    logits = norm(x_L) W_head   over the rows of the vocabulary held here

Departures from the published model, each the configuration file's too:
``Wkv_b`` is drawn as its two halves ``Wk_b`` and ``Wv_b`` (the same
mathematics, a head's columns apart instead of side by side); where the
two ``mla_scale`` factors sit, the selection bias's draw and the
initialisation are the file's ``assumed``.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8_e4m3fn under a per-tensor scale. ``fault`` plants one of
``FAULTS`` in the mathematics: what a comparison has to catch.
``quant="bf16"`` is the WITNESS of the configuration's own precision, no
fault and no control: the same mathematics with both operands of every
product, every product's result and the residual stream rounded to
bfloat16 (norms, softmax and the router's scores stay float32, as the
program keeps them). It reads what bfloat16 alone costs at the cell's
depth, against which the program's own gap is to be read.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib.reference import _Frozen, _quant, make_room  # noqa: F401

Q_BLOCK = 128
_SUB = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo", "w_gate", "w_up",
        "w_down")
LEAVES = tuple(f"{n}{i}" for i in (0, 1) for n in _SUB) \
    + ("w_router", "router_bias", "e_gate", "e_up", "e_down")
FAULTS = ("zero_dropped", "bias_in_weights", "scaling_dropped",
          "sequential_layer", "kv_scale_dropped", "score_scale_row",
          "rope_half_split")
HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["ffn_hidden_size"],
            "Fe": cfg["expert_ffn_hidden_size"],
            "E": cfg["n_routed_experts"],
            "first": cfg.get("expert_first", 0),
            "real": cfg["n_routed_experts_routed"],
            "Z": cfg["zero_expert_num"], "k": cfg["moe_topk"],
            "s": float(cfg["routed_scaling_factor"]),
            "V": cfg["vocab_size"], "L": cfg["num_layers"]}


def _draw(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / jnp.sqrt(fan_in))
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _stack(key, members, shape, fan_in):
    return jnp.stack([_draw(jax.random.fold_in(key, i), shape, fan_in)
                      for i in members])


def embed_weights(seed: int, cfg: dict):
    s = dims(cfg)
    return _draw(jax.random.fold_in(jax.random.PRNGKey(seed), 0),
                 (s["V"], s["d"]), s["d"])


def head_weights(seed: int, cfg: dict):
    s = dims(cfg)
    return _draw(jax.random.fold_in(jax.random.PRNGKey(seed), 1 + s["L"]),
                 (s["d"], s["V"]), s["d"])


def layer_weights(key, cfg: dict, l: int) -> dict:
    """Layer ``l``'s weights from ``key = PRNGKey(seed)``."""
    s = dims(cfg)
    d, H, Fe = s["d"], s["H"], s["Fe"]
    k = dict(zip(LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + l), len(LEAVES))))
    mine = range(s["first"], s["first"] + s["E"])
    n = s["real"] + s["Z"]

    def sub(i):
        kk = {name: k[f"{name}{i}"] for name in _SUB}
        return {
            "wq_a": _draw(kk["wq_a"], (d, s["rq"]), d),
            "wq_b": _draw(kk["wq_b"], (s["rq"], H * (s["dn"] + s["dr"])),
                          s["rq"]),
            "wkv_a": _draw(kk["wkv_a"], (d, s["rkv"] + s["dr"]), d),
            "wk_b": _draw(kk["wk_b"], (s["rkv"], H * s["dn"]), s["rkv"]),
            "wv_b": _draw(kk["wv_b"], (s["rkv"], H * s["dv"]), s["rkv"]),
            "wo": _draw(kk["wo"], (H * s["dv"], d), H * s["dv"]),
            "w_gate": _draw(kk["w_gate"], (d, s["F"]), d),
            "w_up": _draw(kk["w_up"], (d, s["F"]), d),
            "w_down": _draw(kk["w_down"], (s["F"], d), s["F"]),
        }
    return {
        "sub": (sub(0), sub(1)),
        "w_router": _draw(k["w_router"], (d, n), d),
        "router_bias": jax.random.uniform(
            k["router_bias"], (n,), jnp.float32, -1.0 / n, 1.0 / n).astype(
                jnp.bfloat16).astype(jnp.float32),
        "e_gate": _stack(k["e_gate"], mine, (d, Fe), d),
        "e_up": _stack(k["e_up"], mine, (d, Fe), d),
        "e_down": _stack(k["e_down"], mine, (Fe, d), Fe),
    }


def _q(x, mode):
    """An operand of a product at the mode's precision."""
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return _quant(x, mode)


def _kept(x, mode):
    """What the program keeps between products: bfloat16 under the
    witness, the float32 it is everywhere else."""
    return _q(x, mode) if mode == "bf16" else x


def _mm(a, b, mode):
    return _kept(jnp.matmul(_q(a, mode), _q(b, mode), precision=HI), mode)


def _head(h, head, mode):
    """Logits, left in the product's float32 under every mode (as the
    program leaves them: rounded logits would tie at the top)."""
    return jnp.matmul(_q(h, mode), _q(head, mode), precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta, half_split):
    """x: (n, heads, dr), positions 0..n-1, over the whole of dr: channel
    2i rotates with channel 2i+1 (``half_split``, the planted fault: with
    channel i + dr/2)."""
    n, _, dr = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    f = jnp.outer(jnp.arange(n, dtype=jnp.float32), inv)
    c, s = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    if half_split:
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, scale, mode):
    """q, k: (n, H, d_n + d_r); v: (n, H, d_v) -> (n, H, d_v), causal, in
    blocks of query rows."""
    n, H, _ = q.shape
    pad = -n % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, H, q.shape[-1])
    cols = jnp.arange(n)[None, :]

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        sc = jnp.einsum("qhd,nhd->hqn", _q(qi, mode), _q(k, mode),
                        precision=HI) * scale
        p = jax.nn.softmax(jnp.where(cols <= rows, sc, -1e30), axis=-1)
        return _kept(jnp.einsum("hqn,nhd->qhd", _q(p, mode), _q(v, mode),
                                precision=HI), mode)
    o = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    return o.reshape(-1, H, v.shape[-1])[:n]


def _mla(u, w, cfg, mode, fault):
    """u: (n, d), normed -> (n, d): the attention sublayer's addend."""
    s = dims(cfg)
    n, d, H = u.shape[0], s["d"], s["H"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    half = fault == "rope_half_split"
    q = _mm(_rms(_mm(u, w["wq_a"], mode), eps), w["wq_b"], mode) \
        * math.sqrt(d / s["rq"])
    q = q.reshape(n, H, s["dn"] + s["dr"])
    ckr = _mm(u, w["wkv_a"], mode)
    c = _rms(ckr[:, :s["rkv"]], eps)
    if fault != "kv_scale_dropped":
        c = c * math.sqrt(d / s["rkv"])
    kr = _rope(ckr[:, None, s["rkv"]:], theta, half)
    q = jnp.concatenate([q[..., :s["dn"]],
                         _rope(q[..., s["dn"]:], theta, half)], axis=-1)
    k_n = _mm(c, w["wk_b"], mode).reshape(n, H, s["dn"])
    v = _mm(c, w["wv_b"], mode).reshape(n, H, s["dv"])
    k = jnp.concatenate([k_n, jnp.broadcast_to(kr, (n, H, s["dr"]))],
                        axis=-1)
    width = s["rkv"] + s["dr"] if fault == "score_scale_row" \
        else s["dn"] + s["dr"]
    o = _attend(q, k, v, 1.0 / math.sqrt(width), mode)
    return _mm(o.reshape(n, H * s["dv"]), w["wo"], mode)


def _swiglu(y, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(y, wg, mode)) * _mm(y, wu, mode), wd, mode)


def _moe(y, w, cfg, mode, fault):
    """The held experts' part of the routed sum and the identity experts'
    term. y: (n, d)."""
    s = dims(cfg)
    p = jax.nn.softmax(_mm(y, w["w_router"], mode), axis=-1)
    biased = p + w["router_bias"]
    _, top_e = jax.lax.top_k(biased, s["k"])
    top_w = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else p, top_e, axis=-1)
    if fault != "scaling_dropped":
        top_w = top_w * s["s"]

    def one(out, ex):
        i, wg, wu, wd = ex
        w_i = jnp.sum(jnp.where(top_e == s["first"] + i, top_w, 0.0),
                      axis=-1)
        return out + _swiglu(y, wg, wu, wd, mode) * w_i[:, None], None
    # one expert after another over every row (a loop, so that one body is
    # compiled): exact whatever the skew
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(s["E"]), w["e_gate"], w["e_up"], w["e_down"]))
    if fault != "zero_dropped":
        w_zero = jnp.sum(jnp.where(top_e >= s["real"], top_w, 0.0), axis=-1)
        out = out + w_zero[:, None] * y
    return out


def _layer(x, w, cfg, mode, fault):
    """One double layer on ONE sequence. x: (n, d)."""
    eps = cfg["rms_norm_eps"]
    s0, s1 = w["sub"]
    ffn = lambda y, sp: _swiglu(y, sp["w_gate"], sp["w_up"], sp["w_down"],
                                mode)
    a0 = _kept(x + _mla(_rms(x, eps), s0, cfg, mode, fault), mode)
    y0 = _rms(a0, eps)
    m = _kept(_moe(y0, w, cfg, mode, fault), mode)
    b0 = _kept(a0 + ffn(y0, s0), mode)
    if fault == "sequential_layer":
        b0, m = b0 + m, 0.0
    a1 = _kept(b0 + _mla(_rms(b0, eps), s1, cfg, mode, fault), mode)
    return _kept(a1 + ffn(_rms(a1, eps), s1) + m, mode)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4))
_weights_jit = jax.jit(layer_weights, static_argnums=(1, 2))


def _frozen(cfg: dict) -> _Frozen:
    return _Frozen({k: (tuple(v) if isinstance(v, list) else v)
                    for k, v in cfg.items() if not isinstance(v, dict)})


def hiddens(seed: int, cfg: dict, rows, variants=((None, None),)):
    """Final-normed hidden states of every token row under every variant
    ``(quant, fault)``: [variant][row] -> (n, d). One layer's weights are
    alive at a time, shared by all rows and variants."""
    fz = _frozen(cfg)
    embed = embed_weights(seed, cfg)
    xs = [[embed[jnp.asarray(r)] for r in rows] for _ in variants]
    del embed
    for l in range(cfg["num_layers"]):
        w = _weights_jit(jax.random.PRNGKey(seed), fz, l)
        xs = [[_layer_jit(x, w, fz, mode, fault) for x in per]
              for per, (mode, fault) in zip(xs, variants)]
        del w
    return [[_rms(x, cfg["rms_norm_eps"]) for x in per] for per in xs]


def logits(seed: int, cfg: dict, tokens, mode=None, fault=None):
    """(n,) -> (n, vocab held) float32."""
    h = hiddens(seed, cfg, [tokens], ((mode, fault),))[0][0]
    return _head(h, head_weights(seed, cfg), mode)


def served_gaps(seed: int, cfg: dict, sample, pad_to: int,
                variants=()) -> dict:
    """Each sampled request's prompt + served[:-1] through the reference
    once, padded to ``pad_to`` (causal: the padding cannot reach an earlier
    position; one compiled shape). ``gaps``: for each served token, how far
    its reference logit lies below the reference's best at that position
    (>= 0). For each ``(quant, fault)`` of ``variants``, under its name:
    the gap of the token THAT computation puts first at each of those
    positions (the control, the planted faults)."""
    rows, spans = [], []
    for prompt, served in sample:
        n, m = len(prompt), len(served)
        t = np.zeros((pad_to,), np.int32)
        t[:n + m - 1] = np.concatenate([prompt, served[:-1]])
        rows.append(t)
        spans.append((n - 1, n - 1 + m))
    every = ((None, None),) + tuple(variants)
    hs = hiddens(seed, cfg, rows, every)
    head = head_weights(seed, cfg)
    out = {}
    best, ref = [], []
    for (a, b), h in zip(spans, hs[0]):
        lg = _head(h[a:b], head, None)
        ref.append(lg)
        best.append(jnp.max(lg, axis=-1))
    gaps = [bst - jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(served, np.int32))[:, None], axis=-1)[:, 0]
        for bst, lg, (_, served) in zip(best, ref, sample)]
    out["gaps"] = np.concatenate([np.asarray(g) for g in gaps])
    for (mode, fault), per in zip(every[1:], hs[1:]):
        got = []
        for (a, b), h, bst, lg in zip(spans, per, best, ref):
            pick = jnp.argmax(_head(h[a:b], head, mode), axis=-1)
            got.append(np.asarray(bst - jnp.take_along_axis(
                lg, pick[:, None], axis=-1)[:, 0]))
        out[fault or mode] = np.concatenate(got)
    return out
