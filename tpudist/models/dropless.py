"""The held experts' part of a routed sum, DROPLESS with static shapes: the
one routine the expert models of the serve path share (``cohere2moe``:
16 held of 128 routed, width 4096, about one pair an expert a token step;
``sdarmoe``: all 128 held, width 768, about 32 pairs an expert a
forward). The routers stay each model's own.

The pairs of held experts are sorted by expert and cut into blocks of
``block`` rows that belong to one expert each; a loop of as many trips as
there ARE blocks (a ``while`` on the device: the worst case, every pair
on one expert, is correct and slow, the expected case costs what it
routes) gathers a block's tokens, runs the three products against that
expert's weights, picked by branch and read where they lie, and
scatter-adds the weighted result back. No pair of a held expert is
dropped whatever the skew; a pair whose expert lives on another chip adds
nothing here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.scopes import cast, scope

N_STATS = 2     # pairs on held experts, held experts hit

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


def routed(y: jax.Array, top_e, top_w, experts, *, first: int, held: int,
           n_routed: int, real=None):
    """y: (tokens, d); top_e/top_w: (tokens, k), the chosen experts over
    all ``n_routed`` and their weights; ``experts``: ``(gate, up, down)``,
    each a sequence of ``held`` arrays, expert ``first + i``'s at ``i``;
    ``real`` (tokens,) bool: tokens that are none (a prompt's padding, an
    empty slot) route nowhere. -> ((tokens, d) float32, stats)."""
    n, d = y.shape
    k, E = top_e.shape[1], held
    block = block_rows(n, k, n_routed)
    dt = y.dtype
    e_gate, e_up, e_down = experts
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

    out = lax.fori_loop(0, blk_end[-1], one_block,
                        jnp.zeros((n, d), jnp.float32))
    return out, jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)])
