"""A Cohere2-MoE decoder stack for the serve path: one chip's share of it.

What makes it another model than ``transformer`` and ``moe`` (none of it
threaded through their sublayers as flags; the projections, the local
attention and the scopes are theirs):

* a PARALLEL block: attention and the expert mix both read ONE bias-free
  LayerNorm of ``x`` and are added to ``x`` together;
* attention BY LAYER (``layer_kinds``): of every ``full_attn_every`` layers
  the last sees every key and has no positional encoding, the others see
  the last ``sliding_window`` keys (the query's own included) and rotate
  q and k over the whole head in ADJACENT pairs (2i, 2i+1);
* ``head_dim`` is the model's own (128 heads x 128 over hidden 4096), not
  ``d_model / n_heads``;
* the mix: a sigmoid router over ALL ``n_experts``, the top
  ``expert_top_k`` with weights normalised over the chosen, DROPLESS over
  the experts held here (``n_experts_held`` from ``expert_first``): a
  pair whose expert lives on another chip adds nothing here, no pair of
  a held expert is dropped whatever the skew; beside it the MEAN of
  ``n_shared_experts`` shared experts of the same form;
* the head is the embedding, times ``logit_scale``, over the rows held.

Dropless with static shapes: ``models/dropless.py``, the routine this
model shares with ``sdarmoe``.

Two kinds of cache state (``tpudist/serve/kvcache.py``): a full layer
writes and reads the paged pool through the page table, gathering the
pages a slot OWNS (never slots x whole pool); a window layer keeps, per
slot, a ring of the last ``ring`` tokens, written at ``pos mod ring`` and
masked by logical position.

Weights are created at rest in their serving dtype, leaf by leaf, on the
device (``LEAFWISE_INIT``): the float32 whole of this model does not fit
the chip that serves it.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.config import ModelConfig
from tpudist.models import dropless
from tpudist.models import transformer as T
from tpudist.scopes import cast, scope

Params = Dict

# serve.engine reads this: leaves are made in place by ``init`` (never a
# float32 whole)
LEAFWISE_INIT = True

_LEAVES = ("wq", "wk", "wv", "wo", "w_router", "e_gate", "e_up", "e_down",
           "s_gate", "s_up", "s_down")
N_STATS = dropless.N_STATS


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """``sliding`` or ``full`` per layer: local attention first, every
    ``full_attn_every``-th layer full."""
    return tuple("full" if (l + 1) % cfg.full_attn_every == 0 else "sliding"
                 for l in range(cfg.n_layers))


def held(cfg: ModelConfig) -> int:
    return cfg.n_experts_held or cfg.n_experts


# ------------------------------------------------------------------ init


@functools.lru_cache(maxsize=None)
def _maker(sharding):
    def make(key, *, shape, fan_in, dtype, stacked):
        def one(k):
            return (jax.random.normal(k, shape, jnp.float32)
                    * (1.0 / jnp.sqrt(fan_in))).astype(dtype)
        if not stacked:
            return one(key)
        # one member at a time: the float32 draw of a whole stack is
        # never alive
        return lax.map(lambda i: one(jax.random.fold_in(key, i)),
                       jnp.arange(stacked))
    return jax.jit(make, out_shardings=sharding,
                   static_argnames=("shape", "fan_in", "dtype", "stacked"))


def init(key: jax.Array, cfg: ModelConfig, *, dtype=jnp.bfloat16,
         sharding=None) -> Params:
    """normal / sqrt(fan_in), rounded once to ``dtype``, each leaf made by
    a program of its own where it will live. The embedding draws from
    ``fold_in(key, 0)``, layer ``l``'s leaves from the 11 keys split from
    ``fold_in(key, 1 + l)``, expert ``i`` (counted over ALL the model's
    experts, so every chip's share is a part of one model) and shared
    expert ``i`` from ``fold_in(leaf_key, i)``."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    dff, E, S = cfg.d_ff, held(cfg), cfg.n_shared_experts
    make = functools.partial(_maker(sharding), dtype=jnp.dtype(dtype))
    ones = jax.jit(lambda: jnp.ones((d,), jnp.float32),
                   out_shardings=sharding)

    def w(k, *shape, fan_in, stacked=0):
        return make(k, shape=shape, fan_in=fan_in, stacked=stacked)

    def experts(k, *shape, fan_in):
        # an array of its own per expert: the grouped product picks an
        # expert by branch and reads its weights where they lie (indexed
        # out of one stack, each block's three matrices were copied out
        # first: three times the bytes a token step has to move)
        return tuple(w(jax.random.fold_in(k, cfg.expert_first + i), *shape,
                       fan_in=fan_in) for i in range(E))

    layers = []
    for l in range(cfg.n_layers):
        k = dict(zip(_LEAVES, jax.random.split(
            jax.random.fold_in(key, 1 + l), len(_LEAVES))))
        layers.append({
            "norm": ones(),
            "wq": w(k["wq"], d, h * hd, fan_in=d),
            "wk": w(k["wk"], d, kv * hd, fan_in=d),
            "wv": w(k["wv"], d, kv * hd, fan_in=d),
            "wo": w(k["wo"], h * hd, d, fan_in=h * hd),
            "w_router": w(k["w_router"], d, cfg.n_experts, fan_in=d),
            "e_gate": experts(k["e_gate"], d, dff, fan_in=d),
            "e_up": experts(k["e_up"], d, dff, fan_in=d),
            "e_down": experts(k["e_down"], dff, d, fan_in=dff),
            "s_gate": w(k["s_gate"], d, dff, fan_in=d, stacked=S),
            "s_up": w(k["s_up"], d, dff, fan_in=d, stacked=S),
            "s_down": w(k["s_down"], dff, d, fan_in=dff, stacked=S),
        })
    return {"embed": w(jax.random.fold_in(key, 0), cfg.vocab_size, d,
                       fan_in=d),
            "layers": layers, "final_norm": ones()}


# --------------------------------------------------------------- pieces


def layernorm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    """Bias-free LayerNorm, statistics in float32."""
    with scope("norm"):
        xf = x.astype(jnp.float32)
        xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return (xc * lax.rsqrt(var + eps)).astype(x.dtype) * cast(g, x.dtype)


def rope_pairs(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """Rotate ADJACENT channel pairs (2i, 2i+1) of the whole head at each
    token's own position. x: (batch, seq, heads, head_dim); positions:
    (seq,) or (batch, seq). In float32, rounded once. A pair's partner is
    fetched by rotating the lanes (channel 2i takes -x[2i+1], channel 2i+1
    takes x[2i]): no (head_dim/2, 2) view, whose minor dimension of 2 the
    chip would pad to a tile."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.repeat(positions.astype(jnp.float32)[..., None] * inv, 2,
                     axis=-1)
    if positions.ndim == 1:
        ang = ang[None]
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    # the lanes move in x's own dtype (moving is exact); only the two
    # products and their sum are float32
    partner = jnp.where(jnp.arange(hd) % 2 == 0, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return (x.astype(jnp.float32) * c
            + partner.astype(jnp.float32) * s).astype(x.dtype)


def _route(y: jax.Array, lp: Params, cfg: ModelConfig):
    """y: (tokens, d) -> the chosen experts (tokens, k) and their weights,
    normalised over the chosen (float32)."""
    with scope("moe/router"):
        logits = jnp.dot(y, cast(lp["w_router"], y.dtype),
                         preferred_element_type=jnp.float32)
        top_s, top_e = lax.top_k(jax.nn.sigmoid(logits), cfg.expert_top_k)
        return top_e, top_s / jnp.sum(top_s, axis=-1, keepdims=True)


def _routed(y: jax.Array, top_e, top_w, lp: Params, cfg: ModelConfig,
            real=None):
    """The held experts' part of the routed sum: the shared dropless
    routine over this layer's experts. -> ((tokens, d) float32, stats)."""
    return dropless.routed(
        y, top_e, top_w, (lp["e_gate"], lp["e_up"], lp["e_down"]),
        first=cfg.expert_first, held=held(cfg), n_routed=cfg.n_experts,
        real=real)


def _shared(y: jax.Array, lp: Params, cfg: ModelConfig) -> jax.Array:
    """The MEAN of the shared experts' outputs. y: (tokens, d)."""
    dt = y.dtype
    with scope("moe/shared"):
        g = jnp.einsum("td,jdf->jtf", y, cast(lp["s_gate"], dt))
        u = jnp.einsum("td,jdf->jtf", y, cast(lp["s_up"], dt))
        out = jnp.einsum("jtf,jfd->td", jax.nn.silu(g) * u,
                         cast(lp["s_down"], dt),
                         preferred_element_type=jnp.float32)
        return out * (1.0 / cfg.n_shared_experts)


def _mix(y: jax.Array, lp: Params, cfg: ModelConfig, real=None):
    """Routed sum + shared mean of one layer. y: (batch, seq, d) ->
    (same shape and dtype, stats)."""
    b, s, d = y.shape
    y2 = y.reshape(b * s, d)
    top_e, top_w = _route(y2, lp, cfg)
    routed, stats = _routed(y2, top_e, top_w, lp, cfg,
                            None if real is None else real.reshape(-1))
    out = routed + _shared(y2, lp, cfg) if cfg.n_shared_experts else routed
    return out.astype(y.dtype).reshape(b, s, d), stats


def _layer(x, lp: Params, cfg: ModelConfig, attend, real=None):
    """The parallel block. ``attend(q, k, v) -> (o, extra)`` is the one
    thing the forward, the prefill and the decode step differ in."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    y = layernorm(x, lp["norm"], cfg.norm_eps)
    q, k, v = T._qkv(y, lp, b, s, h, kv, hd)
    o, extra = attend(q, k, v)
    m, stats = _mix(y, lp, cfg, real)
    return T._attn_out(x, o.reshape(b, s, h * hd), lp) + m, extra, stats


# ---------------------------------------------------- forward / prefill


def prefill_hidden_states(params: Params, tokens: jax.Array,
                          cfg: ModelConfig, *, dtype, prompt_len=None):
    """Full forward over ``tokens`` (batch, seq) that also hands back what
    a cache is seeded from: each layer's k (rotated where the layer
    rotates) and v, a tuple of (batch, seq, kv, head_dim) per layer, and
    the stats summed over layers. ``prompt_len`` (traced scalar): positions
    from there on are padding and route to no expert.
    -> (h final-normed, ks, vs, stats)."""
    s = tokens.shape[1]
    pos = jnp.arange(s)
    real = None if prompt_len is None else \
        jnp.broadcast_to(pos < prompt_len, tokens.shape)
    x = T.embed_tokens(params, tokens, dtype)
    ks, vs, stats = [], [], jnp.zeros((N_STATS,), jnp.int32)
    for lp, kind in zip(params["layers"], layer_kinds(cfg)):
        def attend(q, k, v, kind=kind):
            if kind == "sliding":
                with scope("attn/rope"):
                    q = rope_pairs(q, pos, cfg.rope_theta)
                    k = rope_pairs(k, pos, cfg.rope_theta)
            with scope("attn/core"):
                o = T._attention(q, k, v, window=cfg.sliding_window
                                 if kind == "sliding" else None)
            return o, (k, v)
        x, (k, v), st = _layer(x, lp, cfg, attend, real)
        ks.append(k)
        vs.append(v)
        stats = stats + st
    h = layernorm(x, params["final_norm"], cfg.norm_eps)
    return h, tuple(ks), tuple(vs), stats


def hidden_states(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                  dtype=jnp.bfloat16) -> jax.Array:
    return prefill_hidden_states(params, tokens, cfg, dtype=dtype)[0]


def tied_logits(params: Params, h: jax.Array, dtype,
                cfg: ModelConfig) -> jax.Array:
    with scope("lm_head"):
        logits = (h @ cast(params["embed"], dtype).T).astype(jnp.float32)
        return logits * cfg.logit_scale


def apply(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
          dtype=jnp.bfloat16) -> jax.Array:
    """tokens (batch, seq) -> logits (batch, seq, vocab held) float32."""
    return tied_logits(params, hidden_states(params, tokens, cfg,
                                             dtype=dtype), dtype, cfg)


# --------------------------------------------------------------- decode


def _scores_to_values(q, kf, vf, mask):
    """q: (slots, window, heads, hd); kf, vf: ``kv`` arrays of (slots,
    keys, hd), one per kv head: a head's keys are scored where they lie,
    as each kind of cache hands them over, and never restacked; mask:
    (slots, window, keys). Grouped-query, float32 softmax."""
    s, w, h, hd = q.shape
    kv = len(kf)
    with scope("attn/core"):
        qg = q.reshape(s, w, kv, h // kv, hd)
        out = []
        for i in range(kv):
            scores = jnp.einsum("swgd,snd->swgn", qg[:, :, i],
                                kf[i].astype(q.dtype)) \
                / jnp.sqrt(jnp.asarray(hd, q.dtype))
            scores = jnp.where(mask[:, :, None, :], scores,
                               jnp.asarray(-1e30, scores.dtype))
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
            out.append(jnp.einsum("swgn,snd->swgd", probs,
                                  vf[i].astype(q.dtype)))
        return jnp.stack(out, axis=2).reshape(s, w, h, hd)


def _owned_pages_attention(q, k_new, v_new, pool_k, pool_v, layer: int,
                           page_table, positions, write_ok,
                           page_tokens: int):
    """A FULL layer's step against the paged pool (the pool's layout and
    the write are ``transformer._paged_attention``'s). The read gathers,
    per slot, the pages its row of the table names, in logical order, so
    a key's position is its place in the gather: what is scored is slots x
    max_pages pages, bounded by what a slot can own, never slots x the
    whole pool. Unmapped entries gather the trash page; they lie past
    every live position and are masked with it."""
    s, w, _, hd = q.shape
    kv, pt = k_new.shape[2], page_tokens
    trash = pool_k.shape[2] - 1
    maxp = page_table.shape[1]
    with scope("attn/kv_write/full"):
        pg = jnp.take_along_axis(page_table, positions // pt, axis=1)
        pg = jnp.where(write_ok & (pg >= 0), pg, trash)
        at = (layer, jnp.arange(kv)[None, None, :], pg[:, :, None],
              (positions % pt)[:, :, None])
        pool_k = pool_k.at[at].set(k_new.astype(pool_k.dtype))
        pool_v = pool_v.at[at].set(v_new.astype(pool_v.dtype))
    with scope("attn/kv_gather/full"):
        rows = jnp.where(page_table >= 0, page_table, trash)  # (s, maxp)

        def owned(pool):    # per kv head (pages+1, pt, hd) -> (s, keys, hd)
            return [pool[layer, i].at[rows].get(
                mode="promise_in_bounds").reshape(s, maxp * pt, hd)
                for i in range(kv)]
        kf, vf = owned(pool_k), owned(pool_v)
        mask = jnp.arange(maxp * pt)[None, None, :] <= positions[:, :, None]
    return _scores_to_values(q, kf, vf, mask), pool_k, pool_v


def _ring_attention(q, k_new, v_new, ring_k, ring_v, positions, write_ok,
                    window: int):
    """A WINDOW layer's step against its per-slot ring (kv, slots, ring,
    hd). Position p is written at ``p mod ring`` (a slot outside the
    dispatch keeps what it had); entry r then holds the newest position
    <= p that is congruent to r, and is read iff that position exists and
    lies in the window."""
    s, w = positions.shape
    kv, ring = k_new.shape[2], ring_k.shape[2]
    with scope("attn/kv_write/window"):
        at = (jnp.arange(kv)[None, None, :], jnp.arange(s)[:, None, None],
              (positions % ring)[:, :, None])
        keep = write_ok[:, :, None, None]
        ring_k = ring_k.at[at].set(jnp.where(
            keep, k_new.astype(ring_k.dtype), ring_k[at]))
        ring_v = ring_v.at[at].set(jnp.where(
            keep, v_new.astype(ring_v.dtype), ring_v[at]))
    with scope("attn/kv_gather/window"):
        back = jnp.mod(positions[:, :, None] - jnp.arange(ring), ring)
        mask = (back < window) & (back <= positions[:, :, None])
    return _scores_to_values(q, list(ring_k), list(ring_v), mask), \
        ring_k, ring_v


def paged_hidden_states(params: Params, tokens: jax.Array,
                        cfg: ModelConfig, *, dtype, pool_k, pool_v,
                        ring_k, ring_v, page_table, positions, write_ok,
                        page_tokens: int):
    """A window of new tokens per slot against both kinds of cache.
    tokens/positions/write_ok: (slots, window); pool_k/pool_v: the full
    layers' paged pool (n_full, kv, pages+1, page_tokens, hd); ring_k/
    ring_v: one ring per window layer, (kv, slots, ring, hd) each.
    -> (h final-normed, pool_k, pool_v, ring_k, ring_v, stats)."""
    x = T.embed_tokens(params, tokens, dtype)
    ring_k, ring_v = list(ring_k), list(ring_v)
    n_full = n_ring = 0
    stats = jnp.zeros((N_STATS,), jnp.int32)
    for lp, kind in zip(params["layers"], layer_kinds(cfg)):
        if kind == "sliding":
            def attend(q, k, v, i=n_ring):
                with scope("attn/rope"):
                    q = rope_pairs(q, positions, cfg.rope_theta)
                    k = rope_pairs(k, positions, cfg.rope_theta)
                o, *cache = _ring_attention(
                    q, k, v, ring_k[i], ring_v[i], positions, write_ok,
                    cfg.sliding_window)
                return o, cache
            x, (ring_k[n_ring], ring_v[n_ring]), st = _layer(
                x, lp, cfg, attend, write_ok)
            n_ring += 1
        else:
            def attend(q, k, v, i=n_full):
                o, *cache = _owned_pages_attention(
                    q, k, v, pool_k, pool_v, i, page_table, positions,
                    write_ok, page_tokens)
                return o, cache
            x, (pool_k, pool_v), st = _layer(x, lp, cfg, attend, write_ok)
            n_full += 1
        stats = stats + st
    h = layernorm(x, params["final_norm"], cfg.norm_eps)
    return h, pool_k, pool_v, tuple(ring_k), tuple(ring_v), stats
