"""Pallas TPU kernel: decode attention over a PAGED KV pool that reads only
the pages its slots own.

The serve path's decode step scores a WINDOW of new tokens per slot (1 for
plain decode, ``speculate_k`` for the verify forward) against that slot's
cached keys. The XLA read it replaces (``models/transformer.
_paged_attention``'s masked read, which stays as the CPU path and as this
kernel's reference) stages a layer's whole page set out of the pool and
scores every slot against every page under an ownership mask: at the
serve cells' sizes 2 x 33.7 MB a layer a token step, five to seven times
the bytes the answer depends on. Here each slot walks its OWN row of the
page table:

  * The pool ``(n_layers, kv, pages+1, page_tokens, head_dim)`` is handed
    in whole and in place (``memory_space=pl.ANY``): no XLA slice in front
    of the call. ``layer``, the page table, the positions and the per-slot
    page counts are scalar-prefetched; every copy is one page of ALL kv
    heads, ``pool[layer, :, table[slot, j]]`` (a strided ``(kv,
    page_tokens, head_dim)`` DMA), ``pages_per_block`` pages to a block,
    double-buffered: block ``i + 1`` (or the next live slot's first) is in
    flight while block ``i`` is scored.
  * Only pages ``j < n_pages[slot]`` are copied, so other slots' pages,
    stale pages and the trash page are never touched and there is no
    ownership mask: a key's position is ``j * page_tokens + offset`` and
    the mask is ``key_pos <= positions[slot, w]`` per query row. A slot
    with no pages is skipped and reads 0.
  * One invocation serves every slot (grid programs run one after the
    other on a TensorCore, each at a cost; the slot loop is inside). A
    block's scores are one ``(window * heads, head_dim) x (head_dim,
    keys)`` matmul a kv head, every query row against that head's keys,
    and the rows of the head's own group are kept: the MXU's time is set
    by the keys it loads, not by the handful of rows, and a group of 1, 2
    or 4 rows never becomes a matmul dimension of its own.
  * Softmax is online in float32 and the values matmul takes the
    probabilities in the query dtype, the discipline of ``_attention`` and
    of the masked read.

The same kernel reads a LATENT cache (``longcatflash``: one row of
``[latent | rope key | dead lanes]`` a token an attention sublayer, the
pool's kv axis 1 and its ``head_dim`` the row's 640 lanes): the caller
hands ONE pool and ``v_width``, and the values are the first ``v_width``
lanes of the key rows where they lie in the key buffer: one copy a page,
no second pool, an output ``v_width`` wide; the softmax scale is an
argument (the published ``(qk_nope + qk_rope) ** -0.5``, not the row's
width). Where the queries and outputs of all slots would not fit fast
memory together (192 slots x 64 heads x 640 lanes), the grid runs GROUPS
of slots (``slot_groups``), each group's queries fetched behind the last
group's work; a group starts its own first copy and starts none for the
next group. Calls that need neither (a K and a V pool, the scale
``head_dim ** -0.5``, one group) trace the program they did before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

# contract the last dimension of both: (m, d) x (t, d) -> (m, t)
_NT = (((1,), (1,)), ((), ()))

# both pools' double buffers together stay under this many bytes
_BUFFER_BYTES = 8 * 1024 * 1024


def _cdiv(a, b):
    return (a + b - 1) // b


def _sublanes(dtype) -> int:
    """Rows of the dtype's minimum (sublane x 128) tile."""
    return 32 // jnp.dtype(dtype).itemsize


def pages_per_block(pool_shape, dtype, max_pages: int) -> int:
    """Pages a copy block holds: as many as keep the four buffers (K and V,
    two each) inside ``_BUFFER_BYTES``, at most 8 (a block is scored whole,
    so a larger one scores more stale keys behind a short slot's last
    page) and at most a slot's row."""
    _, kv, _, pt, hd = pool_shape
    page = kv * pt * hd * jnp.dtype(dtype).itemsize
    return max(1, min(8, max_pages, _BUFFER_BYTES // (4 * page)))


def supports(q_shape, pool_shape, dtype, page_tokens: int,
             v_width: int = 0) -> bool:
    """Shapes the Mosaic lowering takes: lanes full (``head_dim`` a
    multiple of 128), a page a whole number of the dtype's sublane tiles
    (every page lands in its block at a tile boundary), the query rows
    ``window * heads`` a whole number of 8; values read out of the key
    rows (``v_width``) a whole number of lanes of them."""
    _, w, h, hd = q_shape
    kv = pool_shape[1]
    return (hd % 128 == 0 and h % kv == 0
            and page_tokens % _sublanes(dtype) == 0
            and (w * h) % 8 == 0
            and v_width % 128 == 0 and v_width <= hd)


# queries and outputs of one grid step stay under this many bytes (Pallas
# keeps two of each block: the next step's fetched behind this one's work)
_RESIDENT_BYTES = 10 * 1024 * 1024


def slot_groups(slots: int, rows: int, hd: int, out_width: int,
                dtype) -> int:
    """Slots a grid step: all of them where their queries and outputs fit
    ``_RESIDENT_BYTES`` together (every call before the latent one: one
    step, the slot loop inside), else the largest divisor of ``slots``
    that does."""
    per_slot = rows * (hd + out_width) * jnp.dtype(dtype).itemsize
    if slots * per_slot <= _RESIDENT_BYTES:
        return slots
    fit = max(1, (_RESIDENT_BYTES // 2) // per_slot)
    return max(g for g in range(1, slots + 1)
               if slots % g == 0 and g <= fit)


def _kernel(layer_ref, npages_ref, nxt_ref, table_ref, pos_ref,
            q_ref, pk_ref, *rest, slots: int, group: int, w: int, h: int,
            kv: int, pt: int, ppb: int, max_pages: int, scale: float,
            v_width: int):
    if v_width:
        # one pool: the values are the key rows' first ``v_width`` lanes
        (o_ref, kbuf, sems), pv_ref, vbuf = rest, None, None
    else:
        pv_ref, o_ref, kbuf, vbuf, sems = rest
    g = h // kv
    m_rows = w * h
    bk = ppb * pt
    layer = layer_ref[0]
    # float32 operands follow the ambient matmul precision; narrower ones
    # have one native MXU pass, and Mosaic refuses them any other
    precision = None if q_ref.dtype == jnp.float32 \
        else lax.Precision.DEFAULT

    def copies(s, i, buf, act):
        """``act`` on the (K copy, V copy) of every page of block ``i`` of
        slot ``s`` into buffer ``buf`` that the slot's row holds: a page
        past the row is neither started nor waited for."""
        def page(j, carry):
            src = table_ref[s * max_pages + i * ppb + j]
            dst = (buf, slice(None), pl.ds(pl.multiple_of(j * pt, pt), pt))
            act(pltpu.make_async_copy(pk_ref.at[layer, :, src],
                                      kbuf.at[dst], sems.at[0, buf]))
            if pv_ref is not None:
                act(pltpu.make_async_copy(pv_ref.at[layer, :, src],
                                          vbuf.at[dst], sems.at[1, buf]))
            return carry
        # a loop and not ``ppb`` unrolled copies a call site: the kernel's
        # trace and lowering are paid by every process that runs a decode
        # program, compile cache or not
        lax.fori_loop(0, jnp.minimum(npages_ref[s] - i * ppb, ppb), page, 0)

    def start(s, i, buf):
        copies(s, i, buf, lambda c: c.start())

    def wait(s, i, buf):
        copies(s, i, buf, lambda c: c.wait())

    # a page never copied keeps what the buffer held: its keys are masked
    # by a select, its values meet a probability of exactly 0, and 0 x NaN
    # is NaN: the value buffers start finite
    if group == slots:
        lo, end = 0, slots          # one grid step: the program as it was
    else:
        # this step's slots; the buffers and what they hold outlive a step
        lo = pl.program_id(0) * group
        end = lo + group
    # a slot's place in this step's block of queries and outputs
    rel = (lambda s: s) if group == slots else (lambda s: s - lo)
    values = kbuf if vbuf is None else vbuf
    if group == slots:
        values[...] = jnp.zeros_like(values)
    else:
        @pl.when(pl.program_id(0) == 0)
        def _():
            values[...] = jnp.zeros_like(values)

    first = nxt_ref[lo]

    @pl.when(first < end)
    def _():
        start(first, 0, 0)

    # which query position and which kv head a row of the (w * h) rows
    # belongs to, by compares (row = window index * h + head)
    row = lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0)
    row_w = jnp.zeros_like(row)
    for wi in range(1, w):
        row_w += (row >= wi * h).astype(jnp.int32)
    head = row - row_w * h
    row_kv = jnp.zeros_like(row)
    for hk in range(1, kv):
        row_kv += (head >= hk * g).astype(jnp.int32)

    def slot_body(s, buf):
        n_pages = npages_ref[s]
        n_blocks = _cdiv(n_pages, ppb)
        q = q_ref[rel(s)]                                  # (w * h, hd)
        row_pos = jnp.zeros_like(row)
        for wi in range(w):
            row_pos = jnp.where(row_w == wi, pos_ref[s * w + wi], row_pos)

        def block_body(i, carry):
            buf, m, l, acc = carry
            nbuf = 1 - buf

            # in flight while this block is scored: the slot's next
            # block, or behind its last the next live slot's first
            more = i + 1 < n_blocks
            nxt = jnp.where(more, s, nxt_ref[s + 1])

            @pl.when(nxt < end)
            def _():
                start(nxt, jnp.where(more, i + 1, 0), nbuf)

            wait(s, i, buf)
            sc = jnp.zeros((m_rows, bk), jnp.float32)
            for hk in range(kv):
                sc_h = lax.dot_general(q, kbuf[buf, hk], _NT,
                                       precision=precision,
                                       preferred_element_type=jnp.float32)
                sc = jnp.where(row_kv == hk, sc_h, sc)
            key_pos = i * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            mask = (key_pos <= row_pos) & (key_pos < n_pages * pt)
            sc = jnp.where(mask, sc * scale, NEG)
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            p = p.astype(q.dtype)
            pv = jnp.zeros_like(acc)
            for hk in range(kv):
                vals = vbuf[buf, hk] if vbuf is not None \
                    else kbuf[buf, hk, :, :v_width]
                pv_h = jnp.dot(p, vals, precision=precision,
                               preferred_element_type=jnp.float32)
                pv = jnp.where(row_kv == hk, pv_h, pv)
            return nbuf, m_new, l, alpha * acc + pv

        hd = v_width or q.shape[-1]
        buf, _, l, acc = lax.fori_loop(
            0, n_blocks, block_body,
            (buf, jnp.full((m_rows, 1), -jnp.inf, jnp.float32),
             jnp.zeros((m_rows, 1), jnp.float32),
             jnp.zeros((m_rows, hd), jnp.float32)))
        # a slot without pages (l = 0) reads 0, not 0 / 0
        o_ref[rel(s)] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return buf

    lax.fori_loop(lo, end, slot_body, jnp.int32(0))


def walk(page_table, positions, page_tokens: int, n_pool: int):
    """The kernel's scalars, from what the caller holds (a few ops on
    ``(slots, max_pages)`` integers): ``(n_pages, nxt, table, positions)``.

    ``n_pages`` (slots,): per slot the pages up to its LAST query position
    that its row maps without a gap. A live slot's row maps every page up
    to its position, so that is ``ceil((max_w positions + 1) /
    page_tokens)``; a slot whose output the engine discards (inactive,
    outside the dispatch: its row may be stale or -1) stops at the first
    unmapped entry, so every page index the kernel reads is a mapped one.
    ``nxt`` (slots + 1,): the first slot with pages, then for each slot
    the next one after it (``slots`` where there is none): whose first
    block to start while a slot's last is scored. ``table`` and
    ``positions`` flattened, the table clamped into the pool."""
    s, max_pages = page_table.shape
    want = _cdiv(positions.max(axis=1) + 1, page_tokens)
    mapped = jnp.cumprod((page_table >= 0).astype(jnp.int32), axis=1).sum(
        axis=1)
    n_pages = jnp.clip(jnp.minimum(want, mapped), 0, max_pages).astype(
        jnp.int32)
    live = jnp.where(n_pages > 0, jnp.arange(s, dtype=jnp.int32), s)
    nxt = jnp.concatenate([
        lax.cummin(live, reverse=True), jnp.full((1,), s, jnp.int32)])
    table = jnp.clip(page_table, 0, n_pool - 1).astype(jnp.int32)
    return (n_pages, nxt, table.reshape(-1),
            positions.reshape(-1).astype(jnp.int32))


def paged_attention(q, pool_k, pool_v, layer, walked, *, scale=None,
                    v_width: int = 0, block_pages=None, slot_group=None,
                    interpret=False):
    """Attention of ``q`` (slots, window, heads, head_dim), rotated, over
    layer ``layer`` of the pool ``(n_layers, kv, pages+1, page_tokens,
    head_dim)``: slot ``s`` sees the keys of pages ``page_table[s, j]``,
    ``j < n_pages[s]``, at positions ``<= positions[s, w]`` (``walked``
    is :func:`walk` of the table and the positions). Returns (slots,
    window, heads, head_dim) in ``q.dtype``.

    ``scale``: what the scores are multiplied by (default ``head_dim **
    -0.5``). ``v_width`` (then ``pool_v`` is None): the values are the
    first ``v_width`` lanes of the key rows, and the result is (slots,
    window, heads, v_width). ``block_pages`` overrides
    :func:`pages_per_block`, ``slot_group`` :func:`slot_groups`;
    ``interpret`` runs the Pallas interpreter (the CPU tests)."""
    s, w, h, hd = q.shape
    _, kv, _, pt, _ = pool_k.shape
    assert (pool_v is None) == bool(v_width)
    assert pool_v is None or pool_k.shape == pool_v.shape
    n_pages, nxt, table, positions = walked
    max_pages = table.shape[0] // s
    ppb = block_pages or pages_per_block(pool_k.shape, pool_k.dtype,
                                         max_pages)
    out_w = v_width or hd
    group = slot_group or slot_groups(s, w * h, hd, out_w, q.dtype)
    assert s % group == 0
    buffers = pltpu.VMEM((2, kv, ppb * pt, hd), pool_k.dtype)
    pools = (pool_k,) if pool_v is None else (pool_k, pool_v)
    # one step holds every slot's rows; several step through the groups
    at = (lambda *_: (0, 0, 0)) if group == s else \
        (lambda i, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _kernel, slots=s, group=group, w=w, h=h, kv=kv, pt=pt, ppb=ppb,
            max_pages=max_pages, v_width=v_width,
            scale=1.0 / float(hd) ** 0.5 if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s // group,),
            in_specs=[pl.BlockSpec((group, w * h, hd), at)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((group, w * h, out_w), at),
            scratch_shapes=[buffers] * len(pools)
            + [pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((s, w * h, out_w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="paged_attn_decode",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n_pages, nxt, table,
      positions, q.reshape(s, w * h, hd), *pools)
    return out.reshape(s, w, h, out_w)
