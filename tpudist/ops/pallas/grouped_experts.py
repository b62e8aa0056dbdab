"""Pallas TPU kernel: the products of MANY SMALL experts over pairs sorted
by expert, one call a layer a forward.

``models/dropless.routed`` sorts the (token, expert) pairs of the experts
held here by expert and pads every expert's group up to whole tiles of
``block`` rows. The XLA path it keeps for the CPU, for a multi-chip mesh
and for experts too large for fast memory (and which is this kernel's
reference) is a device-side ``while`` of one trip a tile: a dozen small
ops a trip, nothing in flight across trips. At 128 experts of 2048 x 768 a
trip's fixed cost is twice the 11.5 us its 9.4 MB of weights take at the
HBM's rate. Here:

  * The weights rest as ONE stack a leaf, ``(E, d, dff)`` / ``(E, dff,
    d)``, and the grid runs over the tiles. Each weight ``BlockSpec`` takes
    a WHOLE expert at ``tile_expert[i]``, a prefetched scalar: the stack is
    read in place (no slice in front of the call), consecutive tiles of one
    expert keep the block index, so its weights are read once however many
    tiles it has, and the pipeline fetches the next expert's matrices
    behind this tile's products.
  * ``tiles_max`` is static (every pair there could be, plus every
    expert's padding); tiles past the real count repeat the last real
    tile's block indices, which moves nothing, and skip their body. Every
    pair on one expert is many tiles of one block index: correct, and the
    one read.
  * A tile's arithmetic is ``dropless.routed``'s with its roundings where
    they are: ``x @ Wg`` and ``x @ Wu`` accumulated in float32 and rounded
    to the compute dtype, ``silu(g) * u`` in that dtype, ``@ Wd``
    accumulated in float32 and rounded to it.

One expert's three matrices lie in fast memory twice (this tile's and the
next expert's): ``supports`` refuses experts that do not fit so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what the call may ask of a v5e's 128 MiB of fast memory: the weights'
# double buffers, the row blocks' and the tile's intermediates together
_VMEM_BYTES = 64 * 1024 * 1024
# room beside what the shapes account for (Mosaic's own scratch)
_VMEM_SLACK = 4 * 1024 * 1024


def tiles_max(pairs: int, held: int, block: int) -> int:
    """Tiles a call is sized for: every pair on a held expert, and every
    expert's last tile padded by up to ``block - 1`` rows."""
    return -(-(pairs + held * (block - 1)) // block)


def vmem_bytes(d: int, dff: int, block: int, dtype) -> int:
    """Fast memory one call holds: two experts' three matrices (double
    buffered), two row blocks in and two out, the float32 accumulators of
    a tile and their rounded copies."""
    size = jnp.dtype(dtype).itemsize
    weights = 2 * 3 * d * dff * size
    rows = 4 * block * d * size
    tile = block * (2 * dff + d) * (4 + size) + block * dff * size
    return weights + rows + tile


def supports(experts, block: int, dtype) -> bool:
    """What the kernel takes: the weights handed as three STACKS ``(E, d,
    dff)``, ``(E, d, dff)``, ``(E, dff, d)`` (arrays or their shapes; a
    tuple of arrays a leaf is the loop's layout) in the compute dtype,
    lanes full (``d`` and ``dff`` multiples of 128), a block a whole
    number of the dtype's sublane tiles, and one expert's three matrices
    twice in fast memory beside the row blocks."""
    if not all(len(getattr(w, "shape", ())) == 3 and w.dtype == dtype
               for w in experts):
        return False
    _, d, dff = experts[0].shape
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (d % 128 == 0 and dff % 128 == 0 and block % sublanes == 0
            and vmem_bytes(d, dff, block, dtype) <= _VMEM_BYTES)


def _kernel(tile_expert_ref, n_tiles_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref):
    del tile_expert_ref             # the index maps' alone
    dt = x_ref.dtype
    # float32 operands follow the ambient matmul precision; narrower ones
    # have one native MXU pass, and Mosaic refuses them any other
    precision = None if dt == jnp.float32 else lax.Precision.DEFAULT
    dot = functools.partial(jnp.dot, precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        x = x_ref[...]
        # ``silu(g) * u`` on ``dt`` arrays, spelt in float32 (Mosaic
        # lowers no bfloat16 logistic) with a rounding after each of the
        # three ops, where the dtype's own would round
        f32 = lambda a: a.astype(jnp.float32)
        g = f32(dot(x, wg_ref[0]).astype(dt))
        u = f32(dot(x, wu_ref[0]).astype(dt))
        act = f32((g * f32(jax.nn.sigmoid(g).astype(dt))).astype(dt))
        o_ref[...] = dot((act * u).astype(dt), wd_ref[0]).astype(dt)


def grouped_experts(xs, e_gate, e_up, e_down, tile_expert, n_tiles, *,
                    block: int, interpret=False):
    """``xs`` (tiles x block, d): token rows sorted by expert, every
    expert's group padded to whole tiles; ``e_gate``, ``e_up`` (E, d, dff)
    and ``e_down`` (E, dff, d); ``tile_expert`` (tiles,) int32: the expert
    of each tile, a tile past the real count holding the last real tile's;
    ``n_tiles`` (1,) int32: the real count. -> (tiles x block, d) in
    ``xs.dtype``: row ``r`` is its expert's ``silu(x Wg) * (x Wu) @ Wd``;
    rows of tiles past the count are not written. ``interpret`` runs the
    Pallas interpreter (the CPU tests)."""
    rows, d = xs.shape
    E, _, dff = e_gate.shape
    assert e_up.shape == (E, d, dff) and e_down.shape == (E, dff, d)
    tiles = rows // block
    assert tiles * block == rows and tile_expert.shape == (tiles,)

    def tile(i, tile_expert, n_tiles):
        # an idle tile stays on the last real one: nothing is copied in,
        # and the block written back is the one that tile left
        return jnp.maximum(jnp.minimum(i, n_tiles[0] - 1), 0), 0

    def expert(i, tile_expert, n_tiles):
        return tile_expert[i], 0, 0

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((block, d), tile),
                pl.BlockSpec((1, d, dff), expert),
                pl.BlockSpec((1, d, dff), expert),
                pl.BlockSpec((1, dff, d), expert),
            ],
            out_specs=pl.BlockSpec((block, d), tile),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(d, dff, block, xs.dtype)
            + _VMEM_SLACK),
        name="grouped_experts",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32), xs,
      e_gate, e_up, e_down)
