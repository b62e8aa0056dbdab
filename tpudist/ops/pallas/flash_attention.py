"""Pallas TPU kernel: flash attention (fwd + custom-VJP bwd), fused RoPE.

The measured compute hot spot of the transformer workload after the LM head
is attention: the dense path (models/transformer.py::_attention)
materialises the (batch, heads, seq, seq) score tensor in HBM — ~8.5 ms of
the 151 ms bench step per layer on v5e at batch 24/seq 512, against ~0.8 ms
of ideal matmul FLOPs. This kernel streams kv blocks through VMEM with an
online softmax (scores never touch HBM) and recomputes them in the backward
pass (two kernels: dq with kv innermost, dk/dv with q innermost) — the
standard flash-attention schedule, written for the MXU.

Three TPU-specific schedule choices:
  * Pallas grid programs execute **sequentially** on the TensorCore, so
    per-program overhead is paid ``grid-size`` times. A (batch·heads)-sized
    grid dimension at seq 512 means ~1500 programs doing ~0.2 µs of matmul
    each — measured slower than the dense path. Instead, ``block_b``
    batch·head slices are folded into every program as one batched matmul
    on the MXU (``dot_general`` with a batch dimension).
  * Causal masking skips fully-masked blocks: the kv grid dimension is
    innermost, and a block is computed only when its kv columns intersect
    the causal triangle of the q rows (j·block_k ≤ (i+1)·block_q − 1).
  * RoPE is applied INSIDE the kernels (pass ``cos``/``sin``): rotating
    q/k blocks in VMEM removes the rotated tensors' HBM round-trip AND
    their storage as VJP residuals — profiled at ~10 ms/step of loop
    fusions at bench shapes. The backward kernels re-rotate q/k for the
    score recompute and counter-rotate the dq/dk accumulators on the way
    out (the rotation is orthogonal: Rᵀ = R(−θ)).

The reference has no attention anywhere (its model is a 20-feature MLP,
reference train.py:26-36); this kernel serves the north-star transformer
(BASELINE.json config #5). All reductions and accumulations run in f32
regardless of input dtype; matmul operands are cast to the input dtype so
the contractions run native on the MXU with f32 accumulators.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

# The kernels' working set (double-buffered q/k/v/out blocks + f32
# accumulators) exceeds the 16 MiB default scoped-VMEM budget at the
# default block sizes (measured 18 MB at block_b 8, blocks 512). Carrying
# the limit on the pallas_call itself makes the kernels self-contained —
# they compile whether or not the process set
# --xla_tpu_scoped_vmem_limit_kib (tpudist.utils.tune_tpu); v5e VMEM is
# 128 MiB total.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=100 * 1024 * 1024,
)

# dot_general dimension numbers for (nb, m, k) x (nb, n, k) -> (nb, m, n)
_BMM_NT = (((2,), (2,)), ((0,), (0,)))
# (nb, m, k) x (nb, k, n) -> (nb, m, n)
_BMM_NN = (((2,), (1,)), ((0,), (0,)))
# (nb, k, m) x (nb, k, n) -> (nb, m, n)
_BMM_TN = (((1,), (1,)), ((0,), (0,)))


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _expand_rep(x, rep: int):
    """Expand a compact (nb/rep, t, d) kv block to the q-head layout
    (nb, t, d) — inside VMEM, where the copy is registers, not the HBM
    round-trip the old pre-kernel ``jnp.repeat`` paid (r2 advisor
    finding). Consecutive q-head slices share one kv head, matching the
    (batch, head)-flattened index order."""
    if rep == 1:
        return x
    return jnp.repeat(x, rep, axis=0)


def _group_sum(x, rep: int):
    """(nb, t, d) f32 per-q-head partials → compact (nb/rep, t, d) kv-head
    sums: the transpose of :func:`_expand_rep` (exact dk/dv group-sum)."""
    if rep == 1:
        return x
    nb, t, d = x.shape
    return x.reshape(nb // rep, rep, t, d).sum(axis=1)


def _needed(i, j, block_q: int, block_k: int, causal: bool,
            window: int | None = None):
    """Does kv block j intersect the causal triangle of q block i, and,
    with a ``window``, the band of the last ``window`` keys of any of its
    rows (a block wholly behind the band of the block's FIRST row is
    behind every row's)?"""
    if not causal:
        return jnp.bool_(True)
    need = j * block_k <= i * block_q + block_q - 1
    if window is not None:
        need &= j * block_k + block_k - 1 > i * block_q - window
    return need


def _last_j(i, nj, block_q: int, block_k: int, causal: bool):
    """Last kv block q block i consumes (the causal diagonal's block)."""
    if not causal:
        return nj - 1
    return jnp.minimum((i * block_q + block_q - 1) // block_k, nj - 1)


def _rot(x, cos_ref, sin_ref):
    """RoPE-rotate a (nb, t, d) block; cos/sin refs hold (t, d/2)."""
    d2 = x.shape[-1] // 2
    c = cos_ref[:][None].astype(x.dtype)
    s = sin_ref[:][None].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rot_t(x, cos_ref, sin_ref):
    """Transpose (inverse) rotation, for dq/dk cotangents (f32)."""
    d2 = x.shape[-1] // 2
    c = cos_ref[:][None].astype(x.dtype)
    s = sin_ref[:][None].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c + x2 * s, x2 * c - x1 * s], axis=-1)


def _block_scores(q, k, scale, i, j, block_q, block_k, causal,
                  window=None, block=0):
    """(nb, block_q, block_k) f32 scaled scores, causally masked; with a
    ``window`` row r sees the columns c with r - window < c <= r; with a
    ``block`` (a power of two) row r sees every column up to the last of
    its own block of ``block`` positions, ``c <= r | (block - 1)``.

    The mask is applied UNCONDITIONALLY even though only diagonal-
    straddling blocks need it: a scalar ``lax.cond`` skipping it on
    interior blocks was tried (r5) and measured a 16% step REGRESSION at
    seq 8192 (421→489 ms) — the branch materialises ``s`` and breaks
    Mosaic's fusion of the iota/compare/select into the matmul's output
    pipeline, costing far more than the masked elementwise work saves."""
    s = jax.lax.dot_general(q, k, _BMM_NT,
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + i * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) \
            + j * block_k
        keep = cols <= (rows | (block - 1) if block else rows)
        if window is not None:
            keep &= cols > rows - window
        s = jnp.where(keep, s, NEG)
    return s


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale: float, block_q: int, block_k: int,
                causal: bool, rope: bool, single: bool, rep: int,
                window: int | None = None, block: int = 0):
    if rope:
        (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, *scratch) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch = refs
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    if single:
        # One kv block per q block (the grid's kv dim is 1): plain softmax,
        # no online-rescale bookkeeping and no f32 accumulator scratch —
        # measured meaningfully faster than the general path at seq 512
        # (no zero-init pass, no acc read-modify-write, no rescale VPU work)
        q = q_ref[:]
        k = _expand_rep(k_ref[:], rep)
        v = _expand_rep(v_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        s = _block_scores(q, k, scale, i, j, block_q, block_k, causal,
                          window, block)
        m = jnp.max(s, axis=2, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=2, keepdims=True)
        acc = jax.lax.dot_general(p.astype(v.dtype), v, _BMM_NN,
                                  preferred_element_type=jnp.float32)
        o_ref[:] = (acc / l).astype(o_ref.dtype)
        lse_ref[:] = m + jnp.log(l)
        return

    m_ref, l_ref, acc_ref = scratch

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_needed(i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[:]
        k = _expand_rep(k_ref[:], rep)
        v = _expand_rep(v_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        s = _block_scores(q, k, scale, i, j, block_q, block_k, causal,
                          window, block)
        m_prev = m_ref[:]                              # (nb, block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)                         # masked cells → 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _BMM_NN,
            preferred_element_type=jnp.float32)        # (nb, block_q, d)

    # Writing mid-revisit is fine — the out block stays in VMEM until the
    # q index advances.
    @pl.when(j == _last_j(i, nj, block_q, block_k, causal))
    def _finish():
        l = l_ref[:]
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(l)


def _rope_specs(d: int, block_q: int, block_k: int, transposed: bool):
    """cos/sin blockspecs for the q-row and k-row tables: (block, d/2)
    slices of the (s, d/2) tables, indexed by the q (resp. kv) grid dim."""
    d2 = d // 2
    if transposed:      # grid (b, j, i)
        qrow = pl.BlockSpec((block_q, d2), lambda b, j, i: (i, 0),
                            memory_space=pltpu.VMEM)
        krow = pl.BlockSpec((block_k, d2), lambda b, j, i: (j, 0),
                            memory_space=pltpu.VMEM)
    else:               # grid (b, i, j)
        qrow = pl.BlockSpec((block_q, d2), lambda b, i, j: (i, 0),
                            memory_space=pltpu.VMEM)
        krow = pl.BlockSpec((block_k, d2), lambda b, i, j: (j, 0),
                            memory_space=pltpu.VMEM)
    return [qrow, qrow, krow, krow]


def _fwd(q, k, v, cos, sin, *, scale, block_b, block_q, block_k, causal,
         interpret, window=None, block=0) -> Tuple[jax.Array, jax.Array]:
    bh, s, d = q.shape
    sk = k.shape[1]
    rep = bh // k.shape[0]          # grouped-query factor (1 = MHA)
    rope = cos is not None
    grid = (_cdiv(bh, block_b), _cdiv(s, block_q), _cdiv(sk, block_k))

    qspec = pl.BlockSpec((block_b, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    def kv_index(b, i, j):
        if window is None:
            return (b, j, 0)
        # a kv block the band skips is not fetched either: the index is
        # held at the nearest block the row block does use, and a block
        # index that does not move costs no copy
        first = jnp.maximum(i * block_q - window + 1, 0) // block_k
        last = (i * block_q + block_q - 1) // block_k
        return (b, jnp.clip(j, first, last), 0)
    kspec = pl.BlockSpec((block_b // rep, block_k, d), kv_index,
                         memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, kspec]
    args = [q, k, v]
    if rope:
        in_specs += _rope_specs(d, block_q, block_k, transposed=False)
        args += [cos, sin, cos, sin]
    single = grid[2] == 1
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rope=rope,
                          single=single, rep=rep, window=window,
                          block=block),
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            qspec,
            pl.BlockSpec((block_b, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[] if single else [
            pltpu.VMEM((block_b, block_q, 1), jnp.float32),
            pltpu.VMEM((block_b, block_q, 1), jnp.float32),
            pltpu.VMEM((block_b, block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    return o, lse


# --------------------------------------------------------------- backward


def _p_and_ds(q, k, v, do, lse, delta, scale, i, j, block_q, block_k,
              causal, window=None):
    """Recompute the softmax block p and its cotangent ds (both f32).

    ds = p ⊙ (dp − delta) with dp = do·vᵀ — the softmax-jacobian
    contraction folded into the row constant delta = rowsum(do ⊙ o).
    """
    s = _block_scores(q, k, scale, i, j, block_q, block_k, causal, window)
    p = jnp.exp(s - lse)                               # exact softmax
    dp = jax.lax.dot_general(do, v, _BMM_NT,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return p, ds


def _dq_kernel(*refs, scale: float, block_q: int, block_k: int,
                causal: bool, rope: bool, single: bool, rep: int,
                window: int | None = None):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, *scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, *scratch) = refs
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    if single:
        # one kv block per q block: dq in one shot, no accumulator scratch
        q = q_ref[:]
        k = _expand_rep(k_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        _, ds = _p_and_ds(q, k, _expand_rep(v_ref[:], rep), do_ref[:],
                          lse_ref[:], delta_ref[:], scale, i, j, block_q,
                          block_k, causal, window)
        dq = jax.lax.dot_general(ds.astype(k.dtype), k, _BMM_NN,
                                 preferred_element_type=jnp.float32) * scale
        if rope:
            dq = _rot_t(dq, cq_ref, sq_ref)
        dq_ref[:] = dq.astype(dq_ref.dtype)
        return

    acc_ref, = scratch

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_needed(i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[:]
        k = _expand_rep(k_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        _, ds = _p_and_ds(q, k, _expand_rep(v_ref[:], rep), do_ref[:],
                          lse_ref[:], delta_ref[:], scale, i, j, block_q,
                          block_k, causal, window)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _BMM_NN,
            preferred_element_type=jnp.float32)        # (nb, block_q, d)

    @pl.when(j == _last_j(i, nj, block_q, block_k, causal))
    def _finish():
        dq = acc_ref[:] * scale
        if rope:
            # dq was accumulated against rotated k: counter-rotate back to
            # the unrotated-q frame (Rᵀ of the q-row rotation)
            dq = _rot_t(dq, cq_ref, sq_ref)
        dq_ref[:] = dq.astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale: float, block_q: int, block_k: int,
                causal: bool, rope: bool, single: bool, rep: int,
                window: int | None = None):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         cq_ref, sq_ref, ck_ref, sk_ref,
         dk_ref, dv_ref, *scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, *scratch) = refs
    j, i = pl.program_id(1), pl.program_id(2)   # kv outer, q inner
    ni = pl.num_programs(2)

    if single:
        # one q block per kv block: dk/dv in one shot, no accumulators
        q, do = q_ref[:], do_ref[:]
        k = _expand_rep(k_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        p, ds = _p_and_ds(q, k, _expand_rep(v_ref[:], rep), do, lse_ref[:],
                          delta_ref[:], scale, i, j, block_q, block_k,
                          causal, window)
        dv_ref[:] = _group_sum(jax.lax.dot_general(
            p.astype(do.dtype), do, _BMM_TN,
            preferred_element_type=jnp.float32), rep).astype(dv_ref.dtype)
        dk = _group_sum(jax.lax.dot_general(
            ds.astype(q.dtype), q, _BMM_TN,
            preferred_element_type=jnp.float32), rep) * scale
        if rope:
            dk = _rot_t(dk, ck_ref, sk_ref)
        dk_ref[:] = dk.astype(dk_ref.dtype)
        return

    dk_acc, dv_acc = scratch

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_needed(i, j, block_q, block_k, causal, window))
    def _compute():
        q, do = q_ref[:], do_ref[:]
        k = _expand_rep(k_ref[:], rep)
        if rope:
            q = _rot(q, cq_ref, sq_ref)
            k = _rot(k, ck_ref, sk_ref)
        p, ds = _p_and_ds(q, k, _expand_rep(v_ref[:], rep), do, lse_ref[:],
                          delta_ref[:], scale, i, j, block_q, block_k,
                          causal, window)
        # accumulate COMPACT (nb/rep, block_k, d): the group-sum over the
        # rep q-head slices happens here, not as an XLA transpose-of-repeat
        dv_acc[:] += _group_sum(jax.lax.dot_general(
            p.astype(do.dtype), do, _BMM_TN,
            preferred_element_type=jnp.float32), rep)
        dk_acc[:] += _group_sum(jax.lax.dot_general(
            ds.astype(q.dtype), q, _BMM_TN,
            preferred_element_type=jnp.float32), rep)

    # the final q block always attends to every kv block under causality
    @pl.when(i == ni - 1)
    def _finish():
        dk = dk_acc[:] * scale
        if rope:
            dk = _rot_t(dk, ck_ref, sk_ref)
        dk_ref[:] = dk.astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _dqkv_kernel(*refs, scale: float, block_q: int, block_k: int,
                 causal: bool, rope: bool, rep: int,
                 window: int | None = None):
    """Merged single-block backward: when one (q, kv) block pair covers the
    whole sequence, dq/dk/dv come out of ONE p/ds recompute instead of the
    two the split kernels pay (one score matmul, one exp sweep and one
    q/k/v/do block fetch fewer per program) — measured faster at seq 512,
    the headline-bench shape."""
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref) = refs
    q, do = q_ref[:], do_ref[:]
    k = _expand_rep(k_ref[:], rep)
    if rope:
        q = _rot(q, cq_ref, sq_ref)
        k = _rot(k, ck_ref, sk_ref)
    p, ds = _p_and_ds(q, k, _expand_rep(v_ref[:], rep), do, lse_ref[:],
                      delta_ref[:], scale, 0, 0, block_q, block_k, causal,
                      window)
    dq = jax.lax.dot_general(ds.astype(k.dtype), k, _BMM_NN,
                             preferred_element_type=jnp.float32) * scale
    if rope:
        dq = _rot_t(dq, cq_ref, sq_ref)
    dq_ref[:] = dq.astype(dq_ref.dtype)
    dv_ref[:] = _group_sum(jax.lax.dot_general(
        p.astype(do.dtype), do, _BMM_TN,
        preferred_element_type=jnp.float32), rep).astype(dv_ref.dtype)
    dk = _group_sum(jax.lax.dot_general(
        ds.astype(q.dtype), q, _BMM_TN,
        preferred_element_type=jnp.float32), rep) * scale
    if rope:
        dk = _rot_t(dk, ck_ref, sk_ref)
    dk_ref[:] = dk.astype(dk_ref.dtype)


def _bwd(scale, block_b, block_q, block_k, causal, interpret, window, block,
         res, ct):
    if block:
        raise NotImplementedError(
            "flash_attention's block-causal mask is forward only (the "
            "serve path's prefill); the backward kernels mask causally")
    q, k, v, o, lse, cos, sin = res
    do, dlse = ct
    rope = cos is not None
    bh, s, d = q.shape
    bkv, sk = k.shape[0], k.shape[1]
    rep = bh // bkv                 # grouped-query factor (1 = MHA)
    # softmax-jacobian row constant, cheap elementwise fuse outside pallas.
    # An lse cotangent (callers that consume the log-sum-exp, e.g. a ring
    # merge of per-hop partials) folds in exactly here: d lse_i / d s_ij =
    # p_ij, so its score-space contribution is p·dlse — the same shape as
    # the −p·delta term, absorbed as delta − dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # (bh, s, 1)
    delta = delta - dlse.astype(jnp.float32)

    if _cdiv(s, block_q) == 1 and _cdiv(sk, block_k) == 1:
        qspec1 = pl.BlockSpec((block_b, block_q, d), lambda b: (b, 0, 0),
                              memory_space=pltpu.VMEM)
        kspec1 = pl.BlockSpec((block_b // rep, block_k, d),
                              lambda b: (b, 0, 0),
                              memory_space=pltpu.VMEM)
        rowspec1 = pl.BlockSpec((block_b, block_q, 1), lambda b: (b, 0, 0),
                                memory_space=pltpu.VMEM)
        args1 = [q, k, v, do, lse, delta]
        in_specs1 = [qspec1, kspec1, kspec1, qspec1, rowspec1, rowspec1]
        if rope:
            d2 = d // 2
            rspec = pl.BlockSpec((block_q, d2), lambda b: (0, 0),
                                 memory_space=pltpu.VMEM)
            in_specs1 += [rspec, rspec, rspec, rspec]
            args1 += [cos, sin, cos, sin]
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dqkv_kernel, scale=scale, block_q=block_q,
                              block_k=block_k, causal=causal, rope=rope,
                              rep=rep, window=window),
            name="flash_dqkv",
            grid=(_cdiv(bh, block_b),),
            in_specs=in_specs1,
            out_specs=[qspec1, kspec1, kspec1],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                jax.ShapeDtypeStruct((bkv, sk, d), k.dtype),
                jax.ShapeDtypeStruct((bkv, sk, d), v.dtype),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=100 * 1024 * 1024),
        )(*args1)
        dcos = None if cos is None else jnp.zeros_like(cos)
        dsin = None if sin is None else jnp.zeros_like(sin)
        return dq, dk, dv, dcos, dsin

    qspec = pl.BlockSpec((block_b, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((block_b // rep, block_k, d),
                         lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((block_b, block_q, 1),
                           lambda b, i, j: (b, i, 0),
                           memory_space=pltpu.VMEM)
    args = [q, k, v, do, lse, delta]
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    if rope:
        in_specs += _rope_specs(d, block_q, block_k, transposed=False)
        args += [cos, sin, cos, sin]

    single_q = _cdiv(sk, block_k) == 1
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rope=rope,
                          single=single_q, rep=rep, window=window),
        name="flash_dq",
        grid=(_cdiv(bh, block_b), _cdiv(s, block_q), _cdiv(sk, block_k)),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[] if single_q else [
            pltpu.VMEM((block_b, block_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)

    # q innermost: the (nb, block_k, d) accumulators are revisited across
    # all q blocks before the kv index advances
    qspec_t = pl.BlockSpec((block_b, block_q, d), lambda b, j, i: (b, i, 0),
                           memory_space=pltpu.VMEM)
    kspec_t = pl.BlockSpec((block_b // rep, block_k, d),
                           lambda b, j, i: (b, j, 0),
                           memory_space=pltpu.VMEM)
    rowspec_t = pl.BlockSpec((block_b, block_q, 1),
                             lambda b, j, i: (b, i, 0),
                             memory_space=pltpu.VMEM)
    args_t = [q, k, v, do, lse, delta]
    in_specs_t = [qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t, rowspec_t]
    if rope:
        in_specs_t += _rope_specs(d, block_q, block_k, transposed=True)
        args_t += [cos, sin, cos, sin]
    kvout = kspec_t
    single_kv = _cdiv(s, block_q) == 1
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rope=rope,
                          single=single_kv, rep=rep, window=window),
        name="flash_dkv",
        grid=(_cdiv(bh, block_b), _cdiv(sk, block_k), _cdiv(s, block_q)),
        in_specs=in_specs_t,
        out_specs=[kvout, kvout],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, sk, d), v.dtype),
        ],
        scratch_shapes=[] if single_kv else [
            pltpu.VMEM((block_b // rep, block_k, d), jnp.float32),
            pltpu.VMEM((block_b // rep, block_k, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args_t)
    dcos = None if cos is None else jnp.zeros_like(cos)
    dsin = None if sin is None else jnp.zeros_like(sin)
    return dq, dk, dv, dcos, dsin


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, cos, sin, scale, block_b, block_q, block_k, causal,
           interpret, window=None, block=0):
    """Returns (o, lse): BOTH differentiable outputs — lse's cotangent
    folds into the backward's delta constant (see _bwd). Callers that
    ignore lse get a zero dlse from autodiff, which subtracts away."""
    return _fwd(q, k, v, cos, sin, scale=scale, block_b=block_b,
                block_q=block_q, block_k=block_k, causal=causal,
                interpret=interpret, window=window, block=block)


def _flash_fwd(q, k, v, cos, sin, scale, block_b, block_q, block_k,
               causal, interpret, window=None, block=0):
    o, lse = _fwd(q, k, v, cos, sin, scale=scale, block_b=block_b,
                  block_q=block_q, block_k=block_k, causal=causal,
                  interpret=interpret, window=window, block=block)
    return (o, lse), (q, k, v, o, lse, cos, sin)


_flash.defvjp(_flash_fwd, _bwd)


def _pick_block(s: int, preferred: int) -> int | None:
    """Largest MXU-aligned block ≤ preferred that divides s."""
    for b in (preferred, 512, 256, 128):
        if b <= preferred and s % b == 0:
            return b
    return None


def _pick_block_b(bh: int, preferred: int, rep: int = 1) -> int:
    """Largest batch·head fold ≤ preferred dividing bh — and a multiple of
    the grouped-query factor, so every program's q slice covers whole kv
    groups (the compact-kv BlockSpec maps q block b to kv block b).
    ``rep`` always divides bh (rep | h | b·h), so ``rep`` itself is the
    floor."""
    nb = max(preferred, rep)
    while bh % nb or nb % rep:
        nb -= 1
    return nb


def supports(q_shape, k_shape, *, causal: bool = True, block_q: int = 512,
             block_k: int = 512) -> bool:
    """Can flash_attention handle these (b, s, h, hd) shapes? Mirrors every
    ValueError the kernel raises (call sites gate on this and fall back to
    the dense/blockwise paths), including the causal seq_q == seq_k
    requirement — the kernel's mask has no kv-offset notion."""
    _, s, h, hd = q_shape
    _, sk, kv, _ = k_shape
    return (hd % 128 == 0 and h % kv == 0
            and (not causal or s == sk)
            and _pick_block(s, block_q) is not None
            and _pick_block(sk, block_k) is not None)


def _prepare(q, k, v, causal, block_b, block_q, block_k, interpret,
             api_name: str):
    """Shared validation + (b, s, h, hd) → (b·h, s, hd) folding for both
    public entry points (one copy: the shape rules must not drift between
    them). Returns (q3, k3, v3, nb, bq, bk, interpret)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, hd = q.shape
    sk = k.shape[1]
    bq = _pick_block(s, block_q)
    bk = _pick_block(sk, block_k)
    if bq is None or bk is None or hd % 128:
        raise ValueError(
            f"{api_name} needs seq multiples of 128 and head_dim "
            f"multiples of 128, got q {q.shape}, k {k.shape}; gate call "
            f"sites on flash_attention.supports()")
    if causal and s != sk:
        # The causal mask compares unoffset absolute row/col indices, which
        # is wrong for kv-cache/cross-attention offsets (q row i should see
        # kv cols <= i + sk - s). No caller passes such shapes today; fail
        # loudly rather than mask silently wrong (r2 advisor finding).
        raise ValueError(
            f"causal=True requires seq_q == seq_k (got {s} vs {sk}): the "
            f"kernel has no notion of a kv offset")
    if h % k.shape[2]:
        raise ValueError(
            f"heads {h} not divisible by kv_heads {k.shape[2]}")
    rep = h // k.shape[2]
    nb = _pick_block_b(b * h, block_b, rep)

    def to3(x):
        nh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * nh, x.shape[1], hd)

    return to3(q), to3(k), to3(v), nb, bq, bk, interpret


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    cos: jax.Array | None = None,
                    sin: jax.Array | None = None,
                    causal: bool = True, block_b: int = 8,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool | None = None,
                    window: int | None = None,
                    block: int = 0) -> jax.Array:
    """Attention without the (b, h, s, s) score tensor in HBM.

    q: (batch, seq, heads, head_dim); k/v: (batch, seq_k, kv_heads,
    head_dim) — grouped-query k/v stay COMPACT all the way into the
    kernels: the kv BlockSpecs map each q-head block to its kv-head block
    (the q-head fold is constrained to whole kv groups), the expansion
    happens in VMEM, and the dk/dv kernels group-sum back to the compact
    shape — no heads/kv_heads-times copies of k and v ever touch HBM (the
    r2 advisor finding against the old pre-kernel ``jnp.repeat``). Layout
    matches models/transformer.py::_attention, which this replaces on TPU.
    ``cos``/``sin``: optional (seq, head_dim/2) RoPE tables — when given,
    q and k are rotated inside the kernels (see module docstring); the
    tables are positional constants, their cotangent is zero.
    ``block_b`` batch·head slices share one program (sequential-grid
    amortisation); ``interpret=None`` auto-selects the pallas interpreter
    off-TPU so the same code path is CPU-testable. ``window``: query i
    sees keys j with ``i - window < j <= i`` (itself included), forward
    and backward; kv blocks wholly behind the band are neither computed
    nor fetched. Unset, the kernels are the causal ones to the letter.
    ``block`` (a power of two that divides ``block_q``; forward only):
    the mask is causal over BLOCKS of that many positions, query i sees
    keys j with ``j // block <= i // block``, the keys ahead of it inside
    its own block too (a block-diffusion model's prompt). Which kv blocks
    a q block needs does not move: its last row closes a block.
    """
    b, s, h, hd = q.shape
    sk = k.shape[1]
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and at least 1 key (the "
            f"query's own)")
    if block and (not causal or window is not None or block & (block - 1)
                  or 128 % block):
        raise ValueError(
            f"block={block} needs causal=True, no window and a power of "
            f"two that divides every q block")
    if cos is not None and (s != sk or cos.shape != (s, hd // 2)
                            or sin.shape != cos.shape):
        raise ValueError(
            f"rope tables must be (seq, head_dim/2) = ({s}, {hd // 2}) "
            f"with seq == seq_k, got cos {cos.shape}, sin {sin.shape}, "
            f"seq_k {sk}")
    q3, k3, v3, nb, bq, bk, interpret = _prepare(
        q, k, v, causal, block_b, block_q, block_k, interpret,
        "flash_attention")
    cosf = None if cos is None else cos.astype(jnp.float32)
    sinf = None if sin is None else sin.astype(jnp.float32)
    o, _ = _flash(q3, k3, v3, cosf, sinf, 1.0 / (hd ** 0.5),
                  nb, bq, bk, causal, interpret, window, block)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True, block_b: int = 8,
                             block_q: int = 512, block_k: int = 512,
                             interpret: bool | None = None):
    """:func:`flash_attention` that also returns the per-row log-sum-exp.

    Returns (o (b, s, h, hd), lse (b, h, s) f32). lse is DIFFERENTIABLE —
    its cotangent folds into the backward's delta row constant at zero
    extra kernel work — which is what a partial-attention merge needs:
    combining per-hop results (o_i, lse_i) with
    ``lse = logaddexp(...); o = Σ exp(lse_i − lse)·o_i`` backpropagates
    correctly through each hop's kernel. This is the building block for
    ring attention consuming each hop through the flash kernel (future
    work, DESIGN.md); no RoPE fusion here — rotate q/k before calling.
    """
    b, s, h, hd = q.shape
    q3, k3, v3, nb, bq, bk, interpret = _prepare(
        q, k, v, causal, block_b, block_q, block_k, interpret,
        "flash_attention_with_lse")
    o, lse = _flash(q3, k3, v3, None, None, 1.0 / (hd ** 0.5),
                    nb, bq, bk, causal, interpret)
    return (o.reshape(b, h, s, hd).transpose(0, 2, 1, 3),
            lse.reshape(b, h, s))
