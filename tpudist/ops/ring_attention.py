"""Ring attention — context/sequence parallelism over the ``context`` axis.

Long-context extension (the reference has no sequence dimension at all,
SURVEY.md §5.7; this is TPU-first design for scale): the sequence is sharded
over the mesh's ``context`` axis; each device computes flash-style online
softmax for its local query chunks while key/value blocks rotate around the
ring via ``lax.ppermute``. Memory per device is O(S/n) and no device ever
materialises the full S×S score matrix. Each hop's ppermute is issued
*before* the current block is consumed, so the neighbour ICI transfer has no
data dependence on the hop's compute and XLA's scheduler can overlap them.

Causal load balance — the ``zigzag`` layout (default): contiguous sequence
sharding under a causal mask is pathologically imbalanced (rank 0's queries
mask out every remote block; rank n-1 needs them all — and the synchronous
ring makes everyone wait for the busiest rank). Instead the sequence is
split into 2n chunks and rank r holds the PAIR (r, 2n-1-r) — one early
chunk, one late chunk. Under causality exactly two of the four chunk-pairs
per remote hop are live, and both are *fully* unmasked:

  * q_high × k_low — always (the high chunk 2n-1-r is later than every low
    chunk src < n).
  * q_low × k_low(src)  when src < r, else  q_high × k_high(src) — the
    "diagonal" pair, strictly ordered either way.

Only the local block needs masks (intra-chunk causal triangles). Every rank
therefore computes the same 2 chunk-matmuls per hop (3 locally) — ~2× fewer
attention FLOPs than consume-everything and perfectly balanced. The loss is
a token-mean, so the zigzag permutation needs no inverse on the loss path;
callers that need outputs in sequence order apply ``zigzag_inverse``.

Math: standard online-softmax accumulation (numerator, denominator, running
max) in f32; a block fully masked by causality contributes exp(-1e30)=0
rather than -inf arithmetic (NaN-safe).

Hop compute has two implementations, selected by ``use_flash``:

  * **flash** (TPU default when shapes qualify): every hop's chunk
    attention runs in the pallas flash kernel via
    ``flash_attention_with_lse`` and per-hop partials ``(o_i, lse_i)``
    merge with ``lse = logaddexp(...)``, ``o = Σ exp(lse_i − lse)·o_i`` —
    the kernel's lse output is differentiable (its cotangent folds into
    the backward's delta constant), so autodiff through the merge
    backpropagates correctly into each hop's kernel. Scores never touch
    HBM and kv stays compact (GQA) on the ring. Measured on v5e this is
    the difference between kernel speed and XLA-fallback speed in exactly
    the long-context regime CP exists for (README flash-vs-fallback:
    1.5–1.7× at seq 2048–4096).
  * **einsum** (CPU reference + unaligned shapes): f32 einsum hops with
    explicit online-softmax state — the oracle the flash path is tested
    against.
"""

from __future__ import annotations

import functools
import os
from typing import List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

NEG = -1e30


# ------------------------------------------------------------- zigzag layout


def zigzag_order(n: int) -> List[int]:
    """Chunk ids (of 2n sequence chunks) in on-device order: rank r holds
    [r, 2n-1-r], concatenated over ranks."""
    out: List[int] = []
    for r in range(n):
        out += [r, 2 * n - 1 - r]
    return out


def zigzag_permute(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Reorder a sequence axis into zigzag layout: after a contiguous
    n-way shard, rank r's slice holds chunks (r, 2n-1-r) of the original."""
    s = x.shape[axis]
    if s % (2 * n):
        raise ValueError(f"sequence length {s} not divisible by 2*n={2 * n} "
                         "(zigzag context layout)")
    chunks = jnp.split(x, 2 * n, axis=axis)
    return jnp.concatenate([chunks[i] for i in zigzag_order(n)], axis=axis)


def zigzag_inverse(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Inverse of :func:`zigzag_permute`."""
    order = zigzag_order(n)
    inv = [0] * len(order)
    for pos, cid in enumerate(order):
        inv[cid] = pos
    chunks = jnp.split(x, 2 * n, axis=axis)
    return jnp.concatenate([chunks[i] for i in inv], axis=axis)


def zigzag_positions(me, s_local: int, n: int) -> jax.Array:
    """Absolute token positions of rank ``me``'s local zigzag slice
    (chunks me and 2n-1-me), for RoPE. ``me`` may be traced
    (``lax.axis_index``)."""
    c = s_local // 2
    ar = jnp.arange(c)
    return jnp.concatenate([me * c + ar, (2 * n - 1 - me) * c + ar])


# --------------------------------------------------------- online softmax


def _update(scores, vf, num, den, mx):
    """Fold one (b,h,q,k) score block into the (num, den, mx) state."""
    blk_max = jnp.max(scores, axis=-1)                    # (b,h,q)
    new_mx = jnp.maximum(mx, blk_max)
    corr = jnp.exp(mx - new_mx)
    p = jnp.exp(scores - new_mx[..., None])               # (b,h,q,k)
    num = num * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vf)
    den = den * corr + jnp.sum(p, axis=-1)
    return num, den, new_mx


def flash_hops_supported(q_shape, k_shape, *, layout: str = "zigzag",
                         causal: bool = True, n_shards: int = 2) -> bool:
    """Can the per-hop chunk shapes run the flash kernel? Zigzag hops
    operate on half-shard chunks (c = s_local/2); contig hops on whole
    shards; a degenerate size-1 ring (``n_shards=1``) issues exactly one
    whole-shard call, so only that shape must qualify. The causal contig
    schedule masks against a *traced* source rank, which the kernel's
    static mask cannot express — einsum only."""
    from tpudist.ops.pallas import flash_attention as fa
    b, s, h, d = q_shape
    kvh = k_shape[2]
    if n_shards == 1:
        return fa.supports((b, s, h, d), (b, k_shape[1], kvh, d),
                           causal=causal)
    if layout == "zigzag" and causal:
        if s % 2:
            return False
        c = s // 2
        # remote hops: unmasked (c × c); local block: causal (s × s)
        return (fa.supports((b, c, h, d), (b, c, kvh, d), causal=False)
                and fa.supports((b, s, h, d), (b, s, kvh, d), causal=True))
    if not causal:
        return fa.supports((b, s, h, d), (b, k_shape[1], kvh, d),
                           causal=False)
    return False


def _auto_use_flash(q_shape, k_shape, layout: str, causal: bool,
                    n_shards: int) -> bool:
    """TPU default; ``TPUDIST_NO_FLASH=1`` escape hatch;
    ``TPUDIST_RING_FLASH_INTERPRET=1`` opts the CPU interpreter in (tests
    and the multichip dryrun — by default off-TPU stays on the einsum
    reference path, which is the CPU-fast oracle)."""
    if os.environ.get("TPUDIST_NO_FLASH"):
        return False
    if jax.default_backend() != "tpu" \
            and not os.environ.get("TPUDIST_RING_FLASH_INTERPRET"):
        return False
    return flash_hops_supported(q_shape, k_shape, layout=layout,
                                causal=causal, n_shards=n_shards)


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis: str, *, causal: bool = True,
                         layout: str = "zigzag",
                         unroll: int | bool = False,
                         use_flash: bool | None = None,
                         rank=None) -> jax.Array:
    """Per-shard ring attention; call INSIDE shard_map.

    q: local block ``(batch, s_local, heads, head_dim)``; k, v may have
    fewer (grouped-query) kv heads — GQA expansion happens inside the block
    compute, so only the COMPACT kv blocks travel the ring. The sequence
    dim is sharded over ``axis``; with ``layout="zigzag"`` (causal only)
    the caller must have permuted the sequence with :func:`zigzag_permute`.
    Returns the local output block ``(batch, s_local, heads, head_dim)``.

    ``use_flash``: None = auto (flash kernel hops on TPU when the chunk
    shapes qualify, einsum otherwise); True forces the kernel (raising if
    the shapes don't qualify); False forces the einsum reference path.

    ``rank``: this shard's index on ``axis``. None = derive via
    ``lax.axis_index``; the context-parallel loss builders pass it in as
    a sharded-iota input (see models.transformer.make_cp_loss).
    """
    if layout not in ("zigzag", "contig"):
        raise ValueError(f"unknown ring layout {layout!r}")
    n = lax.axis_size(axis)
    if use_flash is None:
        use_flash = _auto_use_flash(q.shape, k.shape, layout, causal, n)
    elif use_flash and not flash_hops_supported(q.shape, k.shape,
                                                layout=layout,
                                                causal=causal, n_shards=n):
        raise ValueError(
            f"use_flash=True but hop shapes q {q.shape} k {k.shape} "
            f"(layout={layout!r}, causal={causal}, n={n}) don't satisfy "
            f"the flash kernel's rules; gate on flash_hops_supported()")
    # n=1 is a degenerate ring (no remote hops): one local kernel call —
    # the zigzag schedule's peeled final hop would re-consume the local
    # block (and the contig-flash init+peel pair would consume it twice)
    if n == 1:
        if use_flash:
            o, _ = _flash_chunk(q, k, v, causal=causal)
            return o.astype(q.dtype)
        return _ring_contig(q, k, v, axis, causal=causal, unroll=unroll,
                            rank=rank)
    if layout == "zigzag" and causal:
        if use_flash:
            return _ring_zigzag_flash(q, k, v, axis, unroll=unroll,
                                      rank=rank)
        return _ring_zigzag(q, k, v, axis, unroll=unroll, rank=rank)
    if use_flash and not causal:
        return _ring_contig_flash(q, k, v, axis, unroll=unroll)
    return _ring_contig(q, k, v, axis, causal=causal, unroll=unroll,
                        rank=rank)


def _expand_gqa(x: jax.Array, rep: int) -> jax.Array:
    xf = x.astype(jnp.float32)
    return jnp.repeat(xf, rep, axis=2) if rep != 1 else xf


def _ring_sweep(k, v, axis: str, state, consume, *, start: int,
                unroll: int | bool = False):
    """Shared ring driver — the scaffolding all four hop implementations
    use (one copy: the r2 degenerate-ring fix showed how peel logic
    drifts when repeated).

    ``consume(i, k_cur, v_cur, state) -> state`` folds hop ``i`` (the
    block that originated ``i`` ranks upstream) into the state. Each
    hop's ppermute of the NEXT block is issued *before* consume, so the
    neighbour ICI transfer has no data dependence on the hop's compute
    and XLA's scheduler overlaps them; the final hop is peeled (consume
    only, nothing left to rotate). ``start=0`` consumes the resident
    local block inside the sweep (contig); ``start=1`` expects the
    caller to have consumed it already (zigzag local specialisation)
    and begins with one rotation."""
    n = lax.axis_size(axis)
    perm = [(j, (j + 1) % n) for j in range(n)]
    if start:
        k = lax.ppermute(k, axis, perm=perm)
        v = lax.ppermute(v, axis, perm=perm)

    def step(i, carry):
        k_cur, v_cur, st = carry
        k_nxt = lax.ppermute(k_cur, axis, perm=perm)
        v_nxt = lax.ppermute(v_cur, axis, perm=perm)
        return k_nxt, v_nxt, consume(i, k_cur, v_cur, st)

    k_l, v_l, state = lax.fori_loop(start, n - 1, step, (k, v, state),
                                    unroll=unroll)
    return consume(n - 1, k_l, v_l, state)


def _ring_contig(q, k, v, axis: str, *, causal: bool,
                 unroll: int | bool = False, rank=None) -> jax.Array:
    """Contiguous-shard ring: every rank consumes every kv block (the only
    option without causality; under causality prefer zigzag)."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis) if rank is None else rank
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.astype(jnp.float32)
    q_pos = me * s + jnp.arange(s)

    def consume(i, k_cur, v_cur, st):
        kf = _expand_gqa(k_cur, rep)
        vf = _expand_gqa(v_cur, rep)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
        if causal:
            k_pos = ((me - i) % n) * s + jnp.arange(s)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG)
        return _update(scores, vf, *st)

    state = (jnp.zeros((b, h, s, d), jnp.float32),
             jnp.zeros((b, h, s), jnp.float32),
             jnp.full((b, h, s), NEG, jnp.float32))
    num, den, _ = _ring_sweep(k, v, axis, state, consume, start=0,
                              unroll=unroll)

    out = num / jnp.maximum(den, 1e-30)[..., None]            # (b,h,q,d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)          # (b,q,h,d)


def _ring_zigzag(q, k, v, axis: str, *,
                 unroll: int | bool = False, rank=None) -> jax.Array:
    """Zigzag-layout causal ring (see module docstring for the schedule)."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis) if rank is None else rank
    b, s, h, d = q.shape
    if s % 2:
        raise ValueError("zigzag layout needs an even local sequence length")
    c = s // 2
    rep = h // k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = q.astype(jnp.float32)
    q_lo, q_hi = qf[:, :c], qf[:, c:]
    tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])[None, None]

    def scores_of(q_chunk, k_chunk, mask=None):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_chunk, k_chunk) * scale
        if mask is not None:
            sc = jnp.where(mask, sc, NEG)
        return sc

    def zero_state():
        return (jnp.zeros((b, h, c, d), jnp.float32),
                jnp.zeros((b, h, c), jnp.float32),
                jnp.full((b, h, c), NEG, jnp.float32))

    # --- local block (the only masked hop): 3 live chunk pairs ---
    kf = _expand_gqa(k, rep)
    vf = _expand_gqa(v, rep)
    k_lo, k_hi = kf[:, :c], kf[:, c:]
    v_lo, v_hi = vf[:, :c], vf[:, c:]
    lo = _update(scores_of(q_lo, k_lo, tri), v_lo, *zero_state())
    hi = _update(scores_of(q_hi, k_lo), v_lo, *zero_state())
    hi = _update(scores_of(q_hi, k_hi, tri), v_hi, *hi)

    def consume_remote(i, k_cur, v_cur, st):
        """Two unmasked chunk pairs: q_hi×k_lo always; the diagonal pair
        goes to q_lo (src < me) or q_hi (src > me) — chunk operands and the
        target state are selected by predicate, the matmuls run once."""
        lo, hi = st
        src = (me - i) % n
        kf = _expand_gqa(k_cur, rep)
        vf = _expand_gqa(v_cur, rep)
        k_lo, k_hi = kf[:, :c], kf[:, c:]
        v_lo, v_hi = vf[:, :c], vf[:, c:]
        hi = _update(scores_of(q_hi, k_lo), v_lo, *hi)

        pred = src < me
        q_sel = jnp.where(pred, q_lo, q_hi)
        k_sel = jnp.where(pred, k_lo, k_hi)
        v_sel = jnp.where(pred, v_lo, v_hi)
        st = jax.tree.map(lambda a, b: jnp.where(pred, a, b), lo, hi)
        st = _update(scores_of(q_sel, k_sel), v_sel, *st)
        lo = jax.tree.map(lambda new, old: jnp.where(pred, new, old), st, lo)
        hi = jax.tree.map(lambda new, old: jnp.where(pred, old, new), st, hi)
        return lo, hi

    # hops 1..n-1: the local block was consumed above (start=1)
    lo, hi = _ring_sweep(k, v, axis, (lo, hi), consume_remote, start=1,
                         unroll=unroll)

    def finish(num, den, mx):
        out = num / jnp.maximum(den, 1e-30)[..., None]        # (b,h,c,d)
        return out.transpose(0, 2, 1, 3)                      # (b,c,h,d)

    return jnp.concatenate([finish(*lo), finish(*hi)],
                           axis=1).astype(q.dtype)


# ----------------------------------------------------- flash-kernel hops


def _flash_chunk(q, k, v, *, causal: bool):
    """One hop's chunk attention through the pallas kernel.

    Returns ``(o, lse)`` with o (b, c, h, d) upcast to f32 — the cross-hop
    merge accumulates in f32 regardless of the kernel's compute dtype —
    and lse (b, h, c) f32. q/k arrive pre-rotated (the CP path applies
    RoPE with per-shard zigzag positions before attention), so the
    kernel's RoPE fusion is not used here."""
    from tpudist.ops.pallas.flash_attention import flash_attention_with_lse
    o, lse = flash_attention_with_lse(q, k, v, causal=causal)
    return o.astype(jnp.float32), lse


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Merge two partial-attention results over disjoint kv sets.

    o: (b, c, h, d) f32 partial outputs; lse: (b, h, c) f32 per-row
    log-sum-exp. ``lse = logaddexp(lse_a, lse_b)`` and the outputs
    combine with weights ``exp(lse_i − lse)`` — exactly the online-softmax
    rescale, expressed on finished partials. Differentiating through this
    merge feeds each hop's kernel backward an (do, dlse) cotangent pair,
    which the kernel folds into its delta row constant (see
    flash_attention._bwd). Also used by the on-chip selfcheck."""
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse).transpose(0, 2, 1)[..., None]
    w_b = jnp.exp(lse_b - lse).transpose(0, 2, 1)[..., None]
    return o_a * w_a + o_b * w_b, lse


def _ring_zigzag_flash(q, k, v, axis: str, *,
                       unroll: int | bool = False, rank=None) -> jax.Array:
    """Zigzag causal ring with every hop in the flash kernel.

    Same schedule as :func:`_ring_zigzag` (see module docstring); the
    per-hop online-softmax state is replaced by finished kernel partials
    (o, lse) merged with :func:`merge_partials`. The local block runs ONE
    causal kernel call over the whole local (lo ++ hi) shard: local index
    order equals absolute position order within the shard, so the plain
    causal mask is exactly the zigzag local mask (lo×lo triangle, hi×lo
    full, lo×hi masked, hi×hi triangle). Remote hops are the two fully
    unmasked chunk calls of the zigzag schedule."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis) if rank is None else rank
    b, s, h, d = q.shape
    if s % 2:
        raise ValueError("zigzag layout needs an even local sequence length")
    c = s // 2
    q_lo, q_hi = q[:, :c], q[:, c:]

    o_loc, lse_loc = _flash_chunk(q, k, v, causal=True)
    lo = (o_loc[:, :c], lse_loc[..., :c])
    hi = (o_loc[:, c:], lse_loc[..., c:])

    def consume_remote(i, k_cur, v_cur, st):
        """Two unmasked kernel calls per hop: q_hi×k_lo always; the
        diagonal pair goes to q_lo (src < me) or q_hi (src > me) —
        operands and target state selected by predicate, the kernel runs
        once (mirrors the einsum schedule)."""
        lo, hi = st
        src = (me - i) % n
        k_lo, k_hi = k_cur[:, :c], k_cur[:, c:]
        v_lo, v_hi = v_cur[:, :c], v_cur[:, c:]
        hi = merge_partials(*hi, *_flash_chunk(q_hi, k_lo, v_lo,
                                               causal=False))

        pred = src < me
        q_sel = jnp.where(pred, q_lo, q_hi)
        k_sel = jnp.where(pred, k_lo, k_hi)
        v_sel = jnp.where(pred, v_lo, v_hi)
        st = jax.tree.map(lambda a, b_: jnp.where(pred, a, b_), lo, hi)
        st = merge_partials(*st, *_flash_chunk(q_sel, k_sel, v_sel,
                                               causal=False))
        lo = jax.tree.map(lambda new, old: jnp.where(pred, new, old), st, lo)
        hi = jax.tree.map(lambda new, old: jnp.where(pred, old, new), st, hi)
        return lo, hi

    # hops 1..n-1: the local block was consumed above (start=1)
    lo, hi = _ring_sweep(k, v, axis, (lo, hi), consume_remote, start=1,
                         unroll=unroll)

    return jnp.concatenate([lo[0], hi[0]], axis=1).astype(q.dtype)


def _ring_contig_flash(q, k, v, axis: str, *,
                       unroll: int | bool = False) -> jax.Array:
    """Non-causal contiguous ring with flash-kernel hops: every hop is a
    fully unmasked whole-shard kernel call, merged by lse. (The causal
    contig schedule masks against a traced source rank — einsum only;
    causal rings use zigzag.)"""
    state = _flash_chunk(q, k, v, causal=False)

    def consume(i, k_cur, v_cur, st):
        return merge_partials(*st, *_flash_chunk(q, k_cur, v_cur,
                                                 causal=False))

    o, _ = _ring_sweep(k, v, axis, state, consume, start=1, unroll=unroll)
    return o.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis: str = "context", *,
                        causal: bool = True, layout: str = "zigzag",
                        use_flash: bool | None = None):
    """Standalone jitted ring attention on globally (seq-)sharded arrays.

    q, k, v: ``(batch, seq, heads, head_dim)`` with seq sharded over
    ``axis``. With the zigzag layout the permutation/inverse are applied
    here, so inputs and outputs are in natural sequence order. Used
    directly by tests and by context-parallel model code.
    """
    n = mesh.shape[axis]
    spec = P(None, axis, None, None)
    zig = layout == "zigzag" and causal and n > 1

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def f(q, k, v):
        return ring_attention_local(q, k, v, axis, causal=causal,
                                    layout=layout, use_flash=use_flash)

    jf = jax.jit(f)

    def apply(q, k, v):
        if zig:
            q, k, v = (zigzag_permute(x, n) for x in (q, k, v))
        sh = NamedSharding(mesh, spec)
        out = jf(jax.device_put(q, sh), jax.device_put(k, sh),
                 jax.device_put(v, sh))
        return zigzag_inverse(out, n) if zig else out
    return apply
