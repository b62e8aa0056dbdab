"""An SDAR-MoE decoder stack for the serve path: a block-diffusion language
model whose every layer is an expert layer, all of its experts held here.

What makes it another model than ``transformer``, ``moe`` and
``cohere2moe`` (the projections, the paged cache and its read, the
dropless expert routine and the scopes are theirs):

* it GENERATES by diffusion over blocks of ``block_length`` positions
  aligned to absolute positions: the logits at position ``i`` score the
  token AT ``i`` (mask-predict, no shift), a position still masked holds
  ``mask_token_id``, and a query sees every key of its own block, the ones
  ahead of it too, and every key of the blocks before it (block-causal).
  The engine (``serve/engine.py``) runs a block through its denoising
  forwards and one commit forward in a dispatch; this module is the
  forward they call;
* a pre-norm SEQUENTIAL block: attention reads ``rmsnorm(x)`` and is added
  to ``x``, the expert mix reads the norm of THAT sum;
* grouped-query attention with a per-head RMSNorm on q and on k (one gain
  of ``head_dim`` for all heads), then RoPE over the whole head in half-
  split pairs ``(i, i + head_dim / 2)``; ``head_dim`` is the model's own;
* the mix: a SOFTMAX router over ``n_experts``, the top ``expert_top_k``
  with weights renormalised over the chosen, no shared expert, dropless
  over all the experts (``models/dropless.py``), whose weights rest as
  one stack a leaf: on one TPU chip the routine runs a layer's experts as
  one grouped Pallas kernel (``ops/pallas/grouped_experts.py``), elsewhere
  as its loop of blocks;
* an untied head over the whole vocabulary.

Weights are created at rest in their serving dtype, leaf by leaf, on the
device (``LEAFWISE_INIT``, as ``cohere2moe``): the float32 whole of this
model does not fit the chip that serves it.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.config import ModelConfig
from tpudist.models import dropless
from tpudist.models import transformer as T
from tpudist.models.cohere2moe import _maker
from tpudist.scopes import cast, scope

Params = Dict

LEAFWISE_INIT = True
# serve.engine reads this too: there is no token-a-step decode of this
# model, a config without a block length is refused in words
GENERATES_BY_BLOCKS = True
N_STATS = dropless.N_STATS
_LEAVES = ("wq", "wk", "wv", "wo", "w_router", "e_gate", "e_up", "e_down")


# ------------------------------------------------------------------ init


def init(key: jax.Array, cfg: ModelConfig, *, dtype=jnp.bfloat16,
         sharding=None) -> Params:
    """normal / sqrt(fan_in), rounded once to ``dtype``, each leaf made by
    a program of its own where it will live. The embedding draws from
    ``fold_in(key, 0)``, the head from ``fold_in(key, 1 + n_layers)``,
    layer ``l``'s leaves from the 8 keys split from ``fold_in(key, 1 +
    l)``, expert ``i``, member ``i`` of its leaf's one stack, from
    ``fold_in(leaf_key, i)``. Every gain is one."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    dff, E, L = cfg.d_ff, cfg.n_experts, cfg.n_layers
    make = functools.partial(_maker(sharding), dtype=jnp.dtype(dtype))
    ones = jax.jit(lambda n: jnp.ones((n,), jnp.float32),
                   out_shardings=sharding, static_argnums=0)

    def w(k, *shape, fan_in, stacked=0):
        return make(k, shape=shape, fan_in=fan_in, stacked=stacked)

    def experts(k, *shape, fan_in):
        # one stack a leaf, member ``i`` drawn from ``fold_in(k, i)``: the
        # grouped kernel reads an expert's matrices out of it in place
        return w(k, *shape, fan_in=fan_in, stacked=E)

    layers = []
    for l in range(L):
        k = dict(zip(_LEAVES, jax.random.split(
            jax.random.fold_in(key, 1 + l), len(_LEAVES))))
        layers.append({
            "attn_norm": ones(d), "ffn_norm": ones(d),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "wq": w(k["wq"], d, h * hd, fan_in=d),
            "wk": w(k["wk"], d, kv * hd, fan_in=d),
            "wv": w(k["wv"], d, kv * hd, fan_in=d),
            "wo": w(k["wo"], h * hd, d, fan_in=h * hd),
            "w_router": w(k["w_router"], d, E, fan_in=d),
            "e_gate": experts(k["e_gate"], d, dff, fan_in=d),
            "e_up": experts(k["e_up"], d, dff, fan_in=d),
            "e_down": experts(k["e_down"], dff, d, fan_in=dff),
        })
    return {"embed": w(jax.random.fold_in(key, 0), cfg.vocab_size, d,
                       fan_in=d),
            "layers": layers, "final_norm": ones(d),
            "head": w(jax.random.fold_in(key, 1 + L), d, cfg.vocab_size,
                      fan_in=d)}


# --------------------------------------------------------------- pieces


def rope_half(x: jax.Array, positions: jax.Array,
              theta: float) -> jax.Array:
    """Rotate the whole head in half-split pairs (i, i + head_dim/2) at
    each token's own position. x: (batch, seq, heads, head_dim);
    positions: (seq,) or (batch, seq). In float32, rounded once."""
    hd = x.shape[-1]
    cos, sin = T.precompute_rope(0, hd, theta,
                                 positions=positions.reshape(-1))
    shape = ((1,) if positions.ndim == 1 else ()) + positions.shape \
        + (1, hd // 2)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _route(y: jax.Array, lp: Params, cfg: ModelConfig):
    """y: (tokens, d) -> the chosen experts (tokens, k) and their weights:
    softmax over all the experts in float32, the top k renormalised."""
    with scope("moe/router"):
        logits = jnp.dot(y, cast(lp["w_router"], y.dtype),
                         preferred_element_type=jnp.float32)
        top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.expert_top_k)
        return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def _mix(y: jax.Array, lp: Params, cfg: ModelConfig, real=None):
    """The routed sum of one layer. y: (batch, seq, d) -> (same shape and
    dtype, stats)."""
    b, s, d = y.shape
    y2 = y.reshape(b * s, d)
    top_e, top_w = _route(y2, lp, cfg)
    out, stats = dropless.routed(
        y2, top_e, top_w, (lp["e_gate"], lp["e_up"], lp["e_down"]),
        first=0, held=cfg.n_experts, n_routed=cfg.n_experts,
        real=None if real is None else real.reshape(-1))
    return out.astype(y.dtype).reshape(b, s, d), stats


def _layer(x, lp: Params, cfg: ModelConfig, positions, attend, real=None):
    """The sequential block. ``attend(q, k, v) -> (o, extra)``, q and k
    normed and rotated, is the one thing the prefill and a block's
    forward differ in."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    a = T.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = T._qkv(a, lp, b, s, h, kv, hd)
    q = T.rmsnorm(q, lp["q_norm"], cfg.norm_eps)
    k = T.rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    with scope("attn/rope"):
        q = rope_half(q, positions, cfg.rope_theta)
        k = rope_half(k, positions, cfg.rope_theta)
    o, extra = attend(q, k, v)
    x = T._attn_out(x, o.reshape(b, s, h * hd), lp)
    m, stats = _mix(T.rmsnorm(x, lp["ffn_norm"], cfg.norm_eps), lp, cfg,
                    real)
    return x + m, extra, stats


# ---------------------------------------------------- forward / prefill


def prefill_hidden_states(params: Params, tokens: jax.Array,
                          cfg: ModelConfig, *, dtype, prompt_len=None):
    """The block-causal forward over ``tokens`` (batch, seq) that also
    hands back what a cache is seeded from: each layer's k (normed and
    rotated) and v, a tuple of (batch, seq, kv, head_dim) per layer, and
    the stats summed over layers. ``prompt_len`` (traced scalar): positions
    from there on are padding and route to no expert.
    -> (h final-normed, ks, vs, stats)."""
    pos = jnp.arange(tokens.shape[1])
    real = None if prompt_len is None else \
        jnp.broadcast_to(pos < prompt_len, tokens.shape)

    def attend(q, k, v):
        with scope("attn/core"):
            return T._attention(q, k, v, block=cfg.block_length), (k, v)

    x = T.embed_tokens(params, tokens, dtype)
    ks, vs, stats = [], [], jnp.zeros((N_STATS,), jnp.int32)
    for lp in params["layers"]:
        x, (k, v), st = _layer(x, lp, cfg, pos, attend, real)
        ks.append(k)
        vs.append(v)
        stats = stats + st
    h = T.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return h, tuple(ks), tuple(vs), stats


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
    """tokens (batch, seq) -> logits (batch, seq, vocab) float32: row i
    scores the token AT position i."""
    return head_logits(params, hidden_states(params, tokens, cfg,
                                             dtype=dtype), dtype)


# ---------------------------------------------------------------- blocks


def paged_hidden_states(params: Params, tokens: jax.Array,
                        cfg: ModelConfig, *, dtype, pool_k, pool_v,
                        page_table, positions, write_ok, see,
                        page_tokens: int):
    """A window of tokens per slot against the paged pool: the forward of
    a block. tokens/positions/write_ok/see: (slots, window); every layer
    writes the window's k and v at ``positions`` and then reads, for each
    row, the slot's keys up to ``see`` (the block's last position: the
    block's own keys are all visible, written a moment before).
    -> (h final-normed, pool_k, pool_v, stats)."""
    x = T.embed_tokens(params, tokens, dtype)
    stats = jnp.zeros((N_STATS,), jnp.int32)
    for layer, lp in enumerate(params["layers"]):
        def attend(q, k, v, layer=layer):
            o, *cache = T._paged_attention(
                q, k, v, pool_k, pool_v, layer, page_table, positions,
                write_ok, page_tokens, see=see)
            return o, cache
        x, (pool_k, pool_v), st = _layer(x, lp, cfg, positions, attend,
                                         write_ok)
        stats = stats + st
    return T.rmsnorm(x, params["final_norm"], cfg.norm_eps), pool_k, \
        pool_v, stats
