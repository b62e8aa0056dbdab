"""A LongCat-Flash decoder stack for the serve path: one chip's share of it.

What makes it another model than ``transformer``, ``cohere2moe`` and
``sdarmoe`` (the flash call, the paged decode kernel, the dropless expert
routine, the leafwise init and the scopes are theirs). With ``norm`` an
RMSNorm with a gain, a layer is a DOUBLE layer, two sets of sublayer
weights 0 and 1 around ONE expert mix::

    a0 = x  + MLA_0(norm_in0(x))
    y0 = norm_post0(a0)
    m  = MoE(y0)                    # the shortcut: computed here, added last
    b0 = a0 + FFN_0(y0)
    a1 = b0 + MLA_1(norm_in1(b0))
    x' = a1 + FFN_1(norm_post1(a1)) + m

* ``MLA``, latent attention. Of a token ``u`` the cache keeps ONE row an
  attention sublayer, ``[c | kr]``: ``c = norm(u Wkv_a[:, :r_kv]) *
  sqrt(d / r_kv)`` and the rope key ``kr`` (the last ``qk_rope_head_dim``
  columns, rotated, shared by all heads); the query is ``q = (norm(u Wq_a)
  Wq_b) * sqrt(d / r_q)``, a head ``[q_n | q_r]``, ``q_r`` rotated. Rotation
  is over the rope dims only, in ADJACENT pairs (2i, 2i+1). Scores are
  ``(q_n . k_n + q_r . kr) / sqrt(qk_nope + qk_rope)``, softmax in float32.
  The PREFILL runs the expanded form (``[k_n,h | v_h] = c Wkv_b`` for every
  head, through ``transformer._attention``: q and k zero-padded from 192
  to 256 lanes and v from 128, q pre-scaled so that the flash kernel's
  ``256 ** -0.5`` gives ``192 ** -0.5``). The DECODE runs the absorbed
  form over the cached rows: ``q~_h = q_n,h W_uk,h^T`` (r_kv wide),
  ``score = (q~_h . c + q_r,h . kr) / sqrt(192)``, ``o_h = (sum_j p c(j))
  W_uv,h``: one key "head" of the row's width for all 64 query heads, the
  values the row's first ``r_kv`` lanes. ``Wkv_b`` rests as its two halves
  (``wk_b``, ``wv_b``), so that neither form slices it.
* ``MoE``: ``p = softmax_f32(y Wr)`` over the ``n_experts`` real experts
  AND the ``n_zero_experts`` identity ones; the top ``expert_top_k`` of
  ``p + b`` (``b``: the selection bias, in the CHOICE only) with weights
  ``routed_scaling * p_e``, not renormalised. A real expert is a SwiGLU of
  width ``d_ff``; DROPLESS over the experts held here
  (``models/dropless.py``; a pair of an expert on another chip, and a pair
  of an identity expert, add nothing there). An identity expert returns its
  input: its pairs add ``(sum of their weights) * y`` here, where the token
  lives, in float32 (scope ``moe/zero``).
* ``FFN``: the same SwiGLU at width ``d_ff_dense``.
* an untied head over the rows of the vocabulary held here.

The latent cache is a kind of ``serve/kvcache.PagedCacheSpec``: a pool
``(sublayers, 1, pages+1, page_tokens, latent_row)`` and no V pool.

Weights are created at rest in their serving dtype, leaf by leaf, on the
device (``LEAFWISE_INIT``): the float32 whole of this model does not fit
the chip that serves it.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.config import ModelConfig
from tpudist.models import dropless
from tpudist.models import transformer as T
from tpudist.models.cohere2moe import _maker, held, rope_pairs
from tpudist.scopes import cast, scope

Params = Dict

LEAFWISE_INIT = True
# dropless's three, then pairs on identity experts and all pairs routed
N_STATS = dropless.N_STATS + 2
# serve.engine.read_stats names what follows dropless's counts, each a
# mean a layer a token step
EXTRA_STATS = ("moe_pairs_zero", "moe_pairs_all")
# the fields of ``ModelConfig`` that are this model's own:
# ``models.model_for`` refuses them set for a model that does not declare
# them too
_WIDTHS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
           "qk_rope_head_dim", "v_head_dim", "d_ff_dense")
CONFIG_FIELDS = _WIDTHS + ("n_zero_experts", "routed_scaling")

_SUB = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo", "w_gate", "w_up",
        "w_down")
_LEAVES = tuple(f"{n}{i}" for i in (0, 1) for n in _SUB) \
    + ("w_router", "router_bias", "e_gate", "e_up", "e_down")


def check_config(cfg: ModelConfig) -> None:
    """The widths of the latent attention and of the dense FFNs have no
    default a model could run at."""
    unset = [f for f in _WIDTHS if getattr(cfg, f) <= 0]
    if unset:
        raise ValueError(
            f"model {cfg.name!r} needs {unset} above 0: they are the "
            f"widths of its latent attention and dense FFNs")


def n_routed(cfg: ModelConfig) -> int:
    """The router's width: real experts and identity ones."""
    return cfg.n_experts + cfg.n_zero_experts


def score_scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


# ------------------------------------------------------------------ init


@functools.lru_cache(maxsize=None)
def _bias_maker(sharding):
    def make(key, *, n, dtype):
        return jax.random.uniform(key, (n,), jnp.float32, -1.0 / n,
                                  1.0 / n).astype(dtype)
    return jax.jit(make, out_shardings=sharding,
                   static_argnames=("n", "dtype"))


def init(key: jax.Array, cfg: ModelConfig, *, dtype=jnp.bfloat16,
         sharding=None) -> Params:
    """normal / sqrt(fan_in), rounded once to ``dtype``, each leaf made by
    a program of its own where it will live. The embedding draws from
    ``fold_in(key, 0)``, the head from ``fold_in(key, 1 + n_layers)``,
    layer ``l``'s leaves from the 23 keys split from ``fold_in(key, 1 +
    l)`` (``_LEAVES``: sublayer 0's nine, sublayer 1's nine, the router,
    its bias, the experts' three), expert ``i``, counted over ALL the
    model's real experts, from ``fold_in(leaf_key, i)``. The selection bias
    is uniform in +-1 / (router width), the scale of a mean score. Every
    gain is one."""
    d, H, L = cfg.d_model, cfg.n_heads, cfg.n_layers
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fe, E = cfg.d_ff_dense, cfg.d_ff, held(cfg)
    dtype = jnp.dtype(dtype)
    make = functools.partial(_maker(sharding), dtype=dtype, stacked=0)
    ones = jax.jit(lambda n: jnp.ones((n,), jnp.float32),
                   out_shardings=sharding, static_argnums=0)

    def w(k, *shape, fan_in):
        return make(k, shape=shape, fan_in=fan_in)

    def experts(k, *shape, fan_in):
        # an array of its own per expert, as ``cohere2moe``: the loop of
        # blocks picks an expert by branch and reads its 75.5 MB in place
        return tuple(w(jax.random.fold_in(k, cfg.expert_first + i), *shape,
                       fan_in=fan_in) for i in range(E))

    def sublayer(k, i):
        k = {n: k[f"{n}{i}"] for n in _SUB}
        return {
            "in_norm": ones(d), "post_norm": ones(d),
            "q_norm": ones(rq), "kv_norm": ones(rkv),
            "wq_a": w(k["wq_a"], d, rq, fan_in=d),
            "wq_b": w(k["wq_b"], rq, H * (dn + dr), fan_in=rq),
            "wkv_a": w(k["wkv_a"], d, rkv + dr, fan_in=d),
            "wk_b": w(k["wk_b"], rkv, H * dn, fan_in=rkv),
            "wv_b": w(k["wv_b"], rkv, H * dv, fan_in=rkv),
            "wo": w(k["wo"], H * dv, d, fan_in=H * dv),
            "w_gate": w(k["w_gate"], d, F, fan_in=d),
            "w_up": w(k["w_up"], d, F, fan_in=d),
            "w_down": w(k["w_down"], F, d, fan_in=F),
        }

    layers = []
    for l in range(L):
        k = dict(zip(_LEAVES, jax.random.split(
            jax.random.fold_in(key, 1 + l), len(_LEAVES))))
        layers.append({
            "sub": (sublayer(k, 0), sublayer(k, 1)),
            "w_router": w(k["w_router"], d, n_routed(cfg), fan_in=d),
            "router_bias": _bias_maker(sharding)(
                k["router_bias"], n=n_routed(cfg), dtype=dtype),
            "e_gate": experts(k["e_gate"], d, Fe, fan_in=d),
            "e_up": experts(k["e_up"], d, Fe, fan_in=d),
            "e_down": experts(k["e_down"], Fe, d, fan_in=Fe),
        })
    return {"embed": w(jax.random.fold_in(key, 0), cfg.vocab_size, d,
                       fan_in=d),
            "layers": layers, "final_norm": ones(d),
            "head": w(jax.random.fold_in(key, 1 + L), d, cfg.vocab_size,
                      fan_in=d)}


# --------------------------------------------------------------- pieces


def _latent_q(u, sp: Params, cfg: ModelConfig, positions, extra: float):
    """u: (batch, seq, d) -> a head's query without rope (batch, seq, H,
    qk_nope) and with, rotated (batch, seq, H, qk_rope), times ``sqrt(d /
    r_q)`` and ``extra``, rounded once from the product's float32."""
    b, s, d = u.shape
    dt, dn = u.dtype, cfg.qk_nope_head_dim
    with scope("attn/latent_q"):
        cq = T.rmsnorm(u @ cast(sp["wq_a"], dt), sp["q_norm"], cfg.norm_eps)
        q = jnp.dot(cq, cast(sp["wq_b"], dt),
                    preferred_element_type=jnp.float32) \
            * (math.sqrt(d / cfg.q_lora_rank) * extra)
        q = q.reshape(b, s, cfg.n_heads, dn + cfg.qk_rope_head_dim)
        q_n = q[..., :dn].astype(dt)
    with scope("attn/rope"):
        q_r = rope_pairs(q[..., dn:], positions, cfg.rope_theta).astype(dt)
    return q_n, q_r


def _latent_row(u, sp: Params, cfg: ModelConfig, positions):
    """u: (batch, seq, d) -> what the cache keeps of each token: the latent
    ``c`` (batch, seq, r_kv), normed and scaled, and the rope key ``kr``
    (batch, seq, qk_rope), rotated."""
    r = cfg.kv_lora_rank
    with scope("attn/latent_kv"):
        ckr = u @ cast(sp["wkv_a"], u.dtype)
        c = T.rmsnorm(ckr[..., :r], sp["kv_norm"], cfg.norm_eps) \
            * jnp.asarray(math.sqrt(u.shape[-1] / r), u.dtype)
    with scope("attn/rope"):
        kr = rope_pairs(ckr[..., r:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]
    return c, kr


def cache_row(c, kr, cfg: ModelConfig):
    """``[c | kr | dead lanes]``: the row as the pool stores it."""
    dead = cfg.latent_row - c.shape[-1] - kr.shape[-1]
    return jnp.concatenate(
        [c, kr, jnp.zeros(c.shape[:-1] + (dead,), c.dtype)], axis=-1)


def _mla_expanded(x, sp: Params, cfg: ModelConfig, positions):
    """The attention sublayer in its EXPANDED form over a whole sequence
    (the prefill): every head's k and v made from the latent, causal.
    x: (batch, seq, d) -> (x + attention, the rows the cache keeps)."""
    b, s, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim, cfg.v_head_dim
    # the one width the flash kernel scores and weighs at
    lanes = -(-(dn + dr) // 128) * 128
    u = T.rmsnorm(x, sp["in_norm"], cfg.norm_eps)
    # its scale is ``lanes ** -0.5``: the published one rides on q
    q_n, q_r = _latent_q(u, sp, cfg, positions,
                         math.sqrt(lanes) * score_scale(cfg))
    c, kr = _latent_row(u, sp, cfg, positions)
    with scope("attn/latent_up"):
        k_n = (c @ cast(sp["wk_b"], c.dtype)).reshape(b, s, H, dn)
        v = (c @ cast(sp["wv_b"], c.dtype)).reshape(b, s, H, dv)
    with scope("attn/core"):
        pad = lambda a: jnp.pad(
            a, ((0, 0),) * 3 + ((0, lanes - a.shape[-1]),))
        q = pad(jnp.concatenate([q_n, q_r], axis=-1))
        k = pad(jnp.concatenate(
            [k_n, jnp.broadcast_to(kr[:, :, None, :], (b, s, H, dr))],
            axis=-1))
        o = T._attention(q, k, pad(v))[..., :dv]
    return T._attn_out(x, o.reshape(b, s, H * dv), sp), cache_row(c, kr, cfg)


def latent_paged_attention(q, row_new, pool, sub: int, page_table,
                           positions, write_ok, page_tokens: int, *,
                           v_width: int, scale: float):
    """The new rows written at their pages, then every query against its
    slot's rows: ``transformer._paged_attention`` for a latent pool
    ``(sublayers, 1, pages+1, page_tokens, row)``. q: (slots, window, H,
    row), already in the latent space; row_new: (slots, window, row). The
    values are the rows' first ``v_width`` lanes. READ by backend and shape
    as there: the Pallas kernel on one TPU chip, the masked read elsewhere.
    -> ((slots, window, H, v_width), pool)."""
    pt, n_pool = page_tokens, pool.shape[2]
    with scope("attn/kv_write"):
        pg = jnp.take_along_axis(page_table, positions // pt, axis=1)
        pg = jnp.where(write_ok & (pg >= 0), pg, n_pool - 1)
        pool = pool.at[sub, 0, pg, positions % pt].set(
            row_new.astype(pool.dtype))
    if T._use_paged_kernel(q.shape, pool.shape, pool.dtype, pt, v_width):
        from tpudist.ops.pallas import paged_attention as pa
        with scope("attn/kv_gather"):
            walked = pa.walk(page_table, positions, pt, n_pool)
        with scope("attn/core"):
            o = pa.paged_attention(q, pool, None, sub, walked, scale=scale,
                                   v_width=v_width)
    else:
        o = T._masked_pool_read(q, pool, None, sub, page_table, positions,
                                pt, scale=scale, v_width=v_width)
    return o, pool


def _mla_absorbed(x, sp: Params, cfg: ModelConfig, sub: int, pool,
                  page_table, positions, write_ok, page_tokens: int):
    """The attention sublayer in its ABSORBED form against the latent pool
    (the decode): the query carried into the latent space, scored against
    the cached rows, the values read carried back out.
    x: (slots, window, d) -> (x + attention, pool)."""
    s, w, _ = x.shape
    H, dn, dv, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, \
        cfg.kv_lora_rank
    dt = x.dtype
    u = T.rmsnorm(x, sp["in_norm"], cfg.norm_eps)
    q_n, q_r = _latent_q(u, sp, cfg, positions, 1.0)
    with scope("attn/latent_q"):
        qt = jnp.einsum("swhn,chn->swhc", q_n,
                        cast(sp["wk_b"], dt).reshape(r, H, dn))
    c, kr = _latent_row(u, sp, cfg, positions)
    dead = cfg.latent_row - r - q_r.shape[-1]
    q = jnp.concatenate([qt, q_r, jnp.zeros((s, w, H, dead), dt)], axis=-1)
    o, pool = latent_paged_attention(
        q, cache_row(c, kr, cfg), pool, sub, page_table, positions,
        write_ok, page_tokens, v_width=r, scale=score_scale(cfg))
    with scope("attn/latent_up"):
        o = jnp.einsum("swhc,chv->swhv", o,
                       cast(sp["wv_b"], dt).reshape(r, H, dv))
    return T._attn_out(x, o.reshape(s, w, H * dv), sp), pool


def _ffn(y, sp: Params):
    """A dense SwiGLU (no residual). y: (batch, seq, d)."""
    dt = y.dtype
    with scope("ffn"):
        gate = jax.nn.silu(y @ cast(sp["w_gate"], dt))
        return (gate * (y @ cast(sp["w_up"], dt))) @ cast(sp["w_down"], dt)


def _route(y: jax.Array, lp: Params, cfg: ModelConfig):
    """y: (tokens, d) -> the chosen experts (tokens, k) over real and
    identity experts and their weights (float32): softmax over all of
    them, the top k of score + bias, each weight its own score times
    ``routed_scaling``."""
    with scope("moe/router"):
        logits = jnp.dot(y, cast(lp["w_router"], y.dtype),
                         preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        _, top_e = lax.top_k(p + lp["router_bias"].astype(jnp.float32),
                             cfg.expert_top_k)
        return top_e, jnp.take_along_axis(p, top_e, axis=-1) \
            * cfg.routed_scaling


def _mix(y: jax.Array, lp: Params, cfg: ModelConfig, real=None):
    """The held experts' part of the routed sum and the identity experts'
    term. y: (batch, seq, d) -> (same shape and dtype, stats (N_STATS,))."""
    b, s, d = y.shape
    y2 = y.reshape(b * s, d)
    top_e, top_w = _route(y2, lp, cfg)
    live = None if real is None else real.reshape(-1)
    routed, stats = dropless.routed(
        y2, top_e, top_w, (lp["e_gate"], lp["e_up"], lp["e_down"]),
        first=cfg.expert_first, held=held(cfg), n_routed=n_routed(cfg),
        real=live)
    with scope("moe/zero"):
        zero = top_e >= cfg.n_experts
        w_zero = jnp.sum(jnp.where(zero, top_w, 0.0), axis=-1)
        out = routed + w_zero[:, None] * y2.astype(jnp.float32)
        if live is None:
            live = jnp.ones((b * s,), bool)
        counts = jnp.stack([jnp.sum(zero & live[:, None]),
                            jnp.sum(live) * cfg.expert_top_k])
    return out.astype(y.dtype).reshape(b, s, d), \
        jnp.concatenate([stats, counts.astype(jnp.int32)])


def _layer(x, lp: Params, cfg: ModelConfig, attend, real=None):
    """The double layer. ``attend(i, x, sp) -> x + MLA_i(norm(x))`` is the
    one thing the prefill and the decode step differ in."""
    s0, s1 = lp["sub"]
    a0 = attend(0, x, s0)
    y0 = T.rmsnorm(a0, s0["post_norm"], cfg.norm_eps)
    m, stats = _mix(y0, lp, cfg, real)
    b0 = a0 + _ffn(y0, s0)
    a1 = attend(1, b0, s1)
    y1 = T.rmsnorm(a1, s1["post_norm"], cfg.norm_eps)
    return a1 + _ffn(y1, s1) + m, stats


# ---------------------------------------------------- forward / prefill


def prefill_hidden_states(params: Params, tokens: jax.Array,
                          cfg: ModelConfig, *, dtype, prompt_len=None):
    """The causal forward over ``tokens`` (batch, seq) that also hands back
    what a cache is seeded from: every attention SUBLAYER's rows, a tuple
    of ``2 * n_layers`` arrays (batch, seq, latent_row), and the stats
    summed over layers. ``prompt_len`` (traced scalar): positions from
    there on are padding and route to no expert.
    -> (h final-normed, rows, stats)."""
    pos = jnp.arange(tokens.shape[1])
    real = None if prompt_len is None else \
        jnp.broadcast_to(pos < prompt_len, tokens.shape)
    rows = []

    def attend(i, x, sp):
        x, row = _mla_expanded(x, sp, cfg, pos)
        rows.append(row)
        return x

    x = T.embed_tokens(params, tokens, dtype)
    stats = jnp.zeros((N_STATS,), jnp.int32)
    for lp in params["layers"]:
        x, st = _layer(x, lp, cfg, attend, real)
        stats = stats + st
    return T.rmsnorm(x, params["final_norm"], cfg.norm_eps), tuple(rows), \
        stats


def hidden_states(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                  dtype=jnp.bfloat16) -> jax.Array:
    return prefill_hidden_states(params, tokens, cfg, dtype=dtype)[0]


def head_logits(params: Params, h: jax.Array, dtype) -> jax.Array:
    """The untied head: final-normed hidden states -> float32 logits."""
    with scope("lm_head"):
        return jnp.dot(h, cast(params["head"], dtype),
                       preferred_element_type=jnp.float32)


def apply(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
          dtype=jnp.bfloat16) -> jax.Array:
    """tokens (batch, seq) -> logits (batch, seq, vocab held) float32."""
    return head_logits(params, hidden_states(params, tokens, cfg,
                                             dtype=dtype), dtype)


# --------------------------------------------------------------- decode


def paged_hidden_states(params: Params, tokens: jax.Array,
                        cfg: ModelConfig, *, dtype, pool, page_table,
                        positions, write_ok, page_tokens: int):
    """A window of new tokens per slot against the latent pool.
    tokens/positions/write_ok: (slots, window); pool: (2 * n_layers, 1,
    pages+1, page_tokens, latent_row), the loop's carry, written in place.
    -> (h final-normed, pool, stats)."""
    x = T.embed_tokens(params, tokens, dtype)
    stats = jnp.zeros((N_STATS,), jnp.int32)
    for layer, lp in enumerate(params["layers"]):
        def attend(i, x, sp, layer=layer):
            nonlocal pool
            x, pool = _mla_absorbed(x, sp, cfg, 2 * layer + i, pool,
                                    page_table, positions, write_ok,
                                    page_tokens)
            return x
        x, st = _layer(x, lp, cfg, attend, write_ok)
        stats = stats + st
    return T.rmsnorm(x, params["final_norm"], cfg.norm_eps), pool, stats
