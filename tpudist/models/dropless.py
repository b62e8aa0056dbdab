"""The held experts' part of a routed sum, DROPLESS with static shapes: the
one routine the expert models of the serve path share (``cohere2moe``:
16 held of 128 routed, width 4096, about one pair an expert a token step;
``sdarmoe``: all 128 held, width 768, about 32 pairs an expert a
forward). The routers stay each model's own.

The pairs of held experts are sorted by expert and cut into blocks of
``block`` rows that belong to one expert each. No pair of a held expert is
dropped whatever the skew; a pair whose expert lives on another chip adds
nothing here; a token that is none routes nowhere. The blocks' products
run one of two ways, chosen by what the code can observe
(:func:`_use_grouped_kernel`; no flag):

* **grouped**: on one TPU chip, where the weights are handed as one stack
  a leaf and one expert's three matrices fit the kernel's fast memory
  twice (``sdarmoe``), one gather lays the sorted rows out in whole
  blocks, the Pallas kernel ``ops/pallas/grouped_experts.py`` runs every
  block's products in one call (an expert's weights read once and in
  place, the next expert's fetched behind this block's products), and each
  pair's row is gathered back through the inverse permutation, weighted
  and summed over a token's k rows in float32: no scatter-add;
* **loop**: everywhere else (the CPU, a multi-chip mesh, experts too large
  for fast memory or lying as an array of their own each: ``cohere2moe``),
  and the kernel's reference: a loop of as many trips as there ARE blocks
  (a ``while`` on the device: the worst case, every pair on one expert, is
  correct and slow, the expected case costs what it routes) gathers a
  block's tokens, runs the three products against that expert's weights,
  picked by branch and read where they lie, and scatter-adds the weighted
  result back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.ops.pallas import grouped_experts as ge
from tpudist.parallel.mesh import one_tpu_program
from tpudist.scopes import cast, scope

N_STATS = 3     # pairs on held experts, held experts hit, blocks of rows run

# rows of one block, at most: enough to keep a long prompt's product on the
# MXU's side of its roofline (a block's products take as long as the read
# of its expert's weights at about peak FLOP/s over HBM bytes/s = 240 rows
# in bfloat16 on a v5e; twice that amortises the gather and the scatter)
_MAX_ROWS = 512
# and at least: below the 240 rows a block costs its expert's weight read
# whatever its rows, so the padding of a small block is free and a second
# trip to the same expert is not
_MIN_ROWS = 32


def block_rows(n: int, k: int, routed: int) -> int:
    """Rows of one expert's block for a forward of ``n`` tokens that
    routes ``k`` pairs a token over ``routed`` experts: twice what an
    expert sees under an even split (room for the skew of a random
    router, so that nearly every expert hit takes ONE trip and its
    weights are read once), a whole number of 16-row tiles, never more
    than the tokens there are. 32 slots' token step at 8 of 128 gives 32,
    an 8192-token prompt 512, 128 slots' blocks of 4 at 8 of 128 give 64,
    a 1024-token prompt 128."""
    tile = lambda r: -(-r // 16) * 16
    want = max(_MIN_ROWS, tile(-(-2 * n * k // routed)))
    return min(tile(n), _MAX_ROWS, want)


def _use_grouped_kernel(experts, block: int, dtype) -> bool:
    """Run the blocks' products as the one Pallas kernel? By what the code
    can observe, the twin of ``transformer._use_paged_kernel``: on one TPU
    chip (``one_tpu_program``: elsewhere the loop stays the path and the
    kernel's reference) at what the kernel supports: the weights handed
    as stacks in the compute dtype, one expert's three matrices twice in
    its fast memory."""
    return one_tpu_program() and ge.supports(experts, block, dtype)


def path(experts, n: int, k: int, n_routed: int, dtype) -> str:
    """``grouped`` or ``loop``: which way :func:`routed` lowers a forward
    of ``n`` tokens in ``dtype`` over these ``experts`` (arrays or their
    shapes), for a program's owner to report."""
    block = block_rows(n, k, n_routed)
    return "grouped" if _use_grouped_kernel(experts, block, dtype) \
        else "loop"


def routed(y: jax.Array, top_e, top_w, experts, *, first: int, held: int,
           n_routed: int, real=None):
    """y: (tokens, d); top_e/top_w: (tokens, k), the chosen experts over
    all ``n_routed`` and their weights; ``experts``: ``(gate, up, down)``,
    each ``held`` matrices, expert ``first + i``'s at ``[i]``: one stack
    or a sequence of arrays; ``real`` (tokens,) bool: tokens that are none
    (a prompt's padding, an empty slot) route nowhere.
    -> ((tokens, d) float32, stats (N_STATS,) int32)."""
    n, k, E = y.shape[0], top_e.shape[1], held
    block = block_rows(n, k, n_routed)
    with scope("moe/dispatch"):
        e = top_e.reshape(-1) - first
        local = (e >= 0) & (e < E)
        if real is not None:
            local &= jnp.repeat(real, k)
        key = jnp.where(local, e, E)
        order = jnp.argsort(key, stable=True)      # held experts first
        sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
        start = jnp.cumsum(sizes) - sizes          # of a group, in order
        nblk = (sizes + block - 1) // block
        blk_end = jnp.cumsum(nblk)
        flat_w = top_w.reshape(-1)
    sort = (key, order, sizes, start, nblk, blk_end, flat_w)
    run = _grouped if _use_grouped_kernel(experts, block, y.dtype) else _loop
    return run(y, experts, sort, k, block), \
        jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), blk_end[-1]])


def _grouped(y, experts, sort, k: int, block: int):
    """Every block's products in one kernel call: the sorted rows laid out
    in whole blocks by one gather, each pair's row gathered back through
    the inverse permutation and a token's k rows summed in float32."""
    key, order, sizes, start, nblk, blk_end, flat_w = sort
    n, E, pairs = y.shape[0], sizes.shape[0], order.shape[0]
    tiles = ge.tiles_max(pairs, E, block)
    with scope("moe/dispatch"):
        blk_start, n_tiles = blk_end - nblk, blk_end[-1]
        t = jnp.arange(tiles)
        # a tile's expert: the groups that end at or before it, counted
        # (a binary search would be a loop on the device); a tile past the
        # real count holds the last real tile's
        ex = jnp.minimum(jnp.sum(
            blk_end[None, :] <= jnp.minimum(t, n_tiles - 1)[:, None],
            axis=1), E - 1)
        # a block's rows past its group's end multiply some token's row
        # for nothing: no pair points at them
        rows = (t - blk_start[ex])[:, None] * block + jnp.arange(block)
        pair = order[jnp.clip(start[ex][:, None] + rows, 0, pairs - 1)]
        xs = y[pair.reshape(-1) // k]
        # where each pair's row lies among the blocks
        rank = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        ek = jnp.minimum(key, E - 1)
        at = jnp.clip(blk_start[ek] * block + rank - start[ek], 0,
                      tiles * block - 1)
    with scope("moe/experts"):
        hs = ge.grouped_experts(xs, *experts, ex, n_tiles[None],
                                block=block)
    with scope("moe/dispatch"):
        # a pair of no held expert points at a row that may never have
        # been written: selected away, not multiplied by 0
        hp = jnp.where((key < E)[:, None],
                       hs[at].astype(jnp.float32) * flat_w[:, None], 0.0)
        return hp.reshape(n, k, -1).sum(axis=1)


def _loop(y, experts, sort, k: int, block: int):
    """One trip a block: gather, the expert's three products picked by
    branch, scatter-add."""
    key, order, sizes, start, nblk, blk_end, flat_w = sort
    n, d = y.shape
    E, dt = sizes.shape[0], y.dtype
    e_gate, e_up, e_down = experts

    def expert(i, xb):
        g = xb @ cast(e_gate[i], dt)
        u = xb @ cast(e_up[i], dt)
        return (jax.nn.silu(g) * u) @ cast(e_down[i], dt)

    def one_block(b, out):
        with scope("moe/dispatch"):
            ex = jnp.searchsorted(blk_end, b, side="right")
            rows = (b - (blk_end[ex] - nblk[ex])) * block + jnp.arange(block)
            ok = rows < sizes[ex]
            pair = order[jnp.clip(start[ex] + rows, 0, n * k - 1)]
            tok = pair // k
            xb = y[tok]
            wb = jnp.where(ok, flat_w[pair], 0.0)
        with scope("moe/experts"):
            hb = lax.switch(ex, [functools.partial(expert, i)
                                 for i in range(E)], xb)
        with scope("moe/dispatch"):
            return out.at[tok].add(hb.astype(jnp.float32) * wb[:, None])

    return lax.fori_loop(0, blk_end[-1], one_block,
                         jnp.zeros((n, d), jnp.float32))
