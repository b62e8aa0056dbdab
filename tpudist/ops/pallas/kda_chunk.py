"""Pallas TPU kernel: the prefill's KDA recurrence in its chunked form,
one call a KDA layer.

``models/kimilinear.chunk_kda`` computes the same thing in XLA (the CPU's
path and this kernel's reference): it transposes q, k, v and g into chunks
a head, builds every chunk's decays and pairs in HBM, solves all chunks'
triangular systems at once and carries the state through a scan, over the
whole pad. Here:

  * The grid runs over (batch, head, chunk), the chunk axis last and
    sequential. A head's float32 state (d_k x d_v) stays in a scratch of
    fast memory for the whole prompt: zero at chunk 0, written to the
    output once, at the last real chunk.
  * q, k, v and g are read in the layout the projections leave them,
    ``(b, T, H d)``, a block of one chunk's rows and a head's columns;
    beta as ``(b, T, H)``. o is written as ``(b, T, H d_v)``. No
    transpose in or out.
  * A chunk's algebra is ``chunk_kda``'s, on data in fast memory: the
    decay summed within the chunk; the pairs of A and M, a pair between
    two blocks of ``sub`` through the reference point at the end of the
    block before the row's, a pair within a block element by element (no
    exponent is ever positive); ``(I + A)^-1`` applied as the inverse of
    its diagonal blocks (a product of powers of a nilpotent matrix, exact)
    and then of the blocks below them (the same, over the blocks); the
    three products with the state and ``M U``. Every operand and
    accumulator is float32 and every product ``lax.Precision.HIGHEST``.
  * ``prompt_len`` comes in as a prefetched scalar. A chunk that starts at
    or past it does no arithmetic: its input blocks stay on the last real
    chunk's (nothing is copied in) and its output block is written as
    zeros, as is every position past ``prompt_len`` in the last real
    chunk. Padding has beta = 0 and g = 0, so it never moves the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = lax.Precision.HIGHEST
# what the call may ask of a v5e's 128 MiB of fast memory: the blocks'
# double buffers, the states and a chunk's intermediates together
_VMEM_BYTES = 32 * 1024 * 1024
# room beside what the shapes account for (Mosaic's own scratch)
_VMEM_SLACK = 4 * 1024 * 1024


def vmem_bytes(K: int, V: int, H: int, chunk: int) -> int:
    """Fast memory one call holds: q, k, g, v and beta blocks in and o out
    (double buffered), a head's state (scratch and output, the output
    double buffered), and a chunk's intermediates: a dozen (chunk, chunk)
    matrices and a dozen (chunk, max(K, V)) ones."""
    f32 = 4
    blocks = 2 * chunk * (3 * K + 2 * V + max(H, 128)) * f32
    states = 3 * K * V * f32
    work = 12 * chunk * (chunk + max(K, V)) * f32
    return blocks + states + work


def supports(k_shape, v_shape, *, chunk: int) -> bool:
    """What the kernel takes: k ``(b, T, H, K)`` and v ``(b, T, H, V)``
    with lanes full (K and V multiples of 128), the pad a whole number of
    chunks, and the blocks, the state and a chunk's intermediates in fast
    memory."""
    b, T, H, K = k_shape
    V = v_shape[-1]
    return (K % 128 == 0 and V % 128 == 0 and T % chunk == 0
            and vmem_bytes(K, V, H, chunk) <= _VMEM_BYTES)


def _mm(a, b, contract=((1,), (0,))):
    return lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _inverse_times(N, X, n: int):
    """``(I + N)^-1 X`` for N with ``N^n = 0``: the product of ``(I +
    (-N)^(2^j))`` over the powers below n, each applied to X."""
    X = X - _mm(N, X)
    P, m = N, 2
    while m < n:
        P = _mm(P, P)
        X = X + _mm(P, X)
        m *= 2
    return X


def _pairs(q, k, gc, *, sub: int):
    """A chunk's pairs: (Pk, M), Pk[t, i] = sum_c k_t k_i exp(gc_t - gc_i)
    for i < t and M[t, i] = sum_c q_t k_i exp(gc_t - gc_i) for i <= t, else
    0. A row block's pairs with the blocks before it are one product
    through the reference point at the end of the block before it; a
    pair within a block is taken element by element, a column at a
    time."""
    C, K = k.shape
    row = lax.broadcasted_iota(jnp.int32, (C, K), 0)
    t_loc = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (sub, C), 1)
    pk, pm = [], []
    for r in range(C // sub):
        rows = slice(r * sub, (r + 1) * sub)
        gr, kr, qr = gc[rows], k[rows], q[rows]
        if r:
            ref = gc[r * sub - 1:r * sub]
            er = jnp.exp(gr - ref)
            left = jnp.concatenate([kr * er, qr * er], axis=0)
            right = k * jnp.exp(jnp.where(row < r * sub, ref - gc, -jnp.inf))
            cross = _mm(left, right, ((1,), (1,)))
            ak, am = cross[:sub], cross[sub:]
        else:
            ak = am = jnp.zeros((sub, C), jnp.float32)
        for j in range(sub):
            i = r * sub + j
            e = jnp.exp(jnp.where(t_loc >= j, gr - gc[i:i + 1], -jnp.inf))
            w = k[i:i + 1] * e
            col_k = jnp.where(t_loc > j,
                              jnp.sum(kr * w, axis=1, keepdims=True), 0.0)
            col_q = jnp.sum(qr * w, axis=1, keepdims=True)
            ak = jnp.where(lane == i, col_k, ak)
            am = jnp.where(lane == i, col_q, am)
        pk.append(ak)
        pm.append(am)
    return jnp.concatenate(pk, axis=0), jnp.concatenate(pm, axis=0)


def _chunk(q, k, v, g, beta, S, *, sub: int):
    """One chunk of one head from state S: ``chunk_kda``'s step, with
    ``U = (I + A)^-1 Diag(beta) (V - (exp(gc) * K) S)``. -> (O, new S)."""
    C, K = k.shape
    r = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    c = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    gc = _mm(jnp.where(c <= r, 1.0, 0.0).astype(jnp.float32), g)
    pk, M = _pairs(q, k, gc, sub=sub)
    A = beta * pk
    # I + A = (I + D)(I + T F): D its diagonal blocks, T = (I + D)^-1, F
    # the blocks below them; D^sub = 0 and (T F)^(C / sub) = 0
    own = (r // sub) == (c // sub)
    T = _inverse_times(jnp.where(own, A, 0.0),
                       jnp.where(r == c, 1.0, 0.0).astype(jnp.float32), sub)
    G = _mm(T, jnp.where(own, 0.0, A))
    eg = jnp.exp(gc)
    R = beta * (v - _mm(k * eg, S))
    U = _inverse_times(G, _mm(T, R), C // sub)
    O = _mm(q * eg, S) + _mm(M, U)
    last = gc[C - 1:C]
    # the chunk's decay as a column: a row of S is a key channel
    decay = jnp.transpose(jnp.broadcast_to(jnp.exp(last), (8, K)))[:, :1]
    S = decay * S + _mm(k * jnp.exp(last - gc), U, ((0,), (0,)))
    return O, S


def _kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref,
            state, *, chunk: int, sub: int):
    h, c = pl.program_id(1), pl.program_id(2)
    n = len_ref[0]
    real = jnp.maximum(pl.cdiv(n, chunk), 1)

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @pl.when(c >= real)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c < real)
    def _():
        # this head's beta: its one column of the block's (chunk, H)
        lane = lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
        beta = jnp.sum(jnp.where(lane == h, beta_ref[...], 0.0), axis=1,
                       keepdims=True)
        O, state[...] = _chunk(q_ref[...], k_ref[...], v_ref[...],
                               g_ref[...], beta, state[...], sub=sub)
        pos = c * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        o_ref[...] = jnp.where(pos < n, O, 0.0)

        @pl.when(c == real - 1)
        def _():
            s_ref[...] = state[...]


# one trace and one lowering however many layers call it
@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def kda_chunk(q, k, v, g, beta, prompt_len, *, chunk: int, sub: int,
              interpret=False):
    """q (scaled), k, g: ``(b, T, H K)``; v: ``(b, T, H V)``; beta ``(b,
    T, H)``; all float32, positions from ``prompt_len`` (a traced int) on
    padding with beta = 0 and g = 0. -> (o ``(b, T, H V)``, zero at every
    position past ``prompt_len``; the final state ``(b, H, K, V)``), from
    a zero state. ``interpret`` runs the Pallas interpreter (the CPU
    tests)."""
    b, T, H = beta.shape
    K, V = k.shape[-1] // H, v.shape[-1] // H
    n = T // chunk
    assert n * chunk == T

    def rows(bi, h, c, lens):
        # a chunk past the prompt stays on the last real one: no copy
        last = jnp.maximum(pl.cdiv(lens[0], chunk), 1) - 1
        return bi, jnp.minimum(c, last), h

    def beta_rows(bi, h, c, lens):
        return rows(bi, h, c, lens)[:2] + (0,)

    qk = pl.BlockSpec((None, chunk, K), rows)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H, n),
            in_specs=[qk, qk, pl.BlockSpec((None, chunk, V), rows), qk,
                      pl.BlockSpec((None, chunk, H), beta_rows)],
            out_specs=[
                pl.BlockSpec((None, chunk, V),
                             lambda bi, h, c, lens: (bi, c, h)),
                pl.BlockSpec((None, None, K, V),
                             lambda bi, h, c, lens: (bi, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, T, H * V), jnp.float32),
                   jax.ShapeDtypeStruct((b, H, K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(K, V, H, chunk) + _VMEM_SLACK),
        name="kda_chunk",
        interpret=interpret,
    )(jnp.reshape(prompt_len, (1,)).astype(jnp.int32), q, k, v, g, beta)
