"""Ulysses-style sequence parallelism: all-to-all head↔sequence reshard.

The second standard long-context strategy next to ring attention
(tpudist.ops.ring_attention): instead of rotating key/value blocks around
a ring, TWO ``lax.all_to_all`` collectives reshard the activations so each
device sees the FULL sequence for a slice of the heads —

    (batch, s/n, heads, hd)  --all_to_all-->  (batch, s, heads/n, hd)
        attention over the full sequence, local heads only
    (batch, s, heads/n, hd)  --all_to_all-->  (batch, s/n, heads, hd)

Attention math is then exactly the single-device kernel (dense, blockwise,
or the pallas flash kernel — whatever ``_attention`` routes to), with no
masking games and perfect causal load balance; sequence shards stay
CONTIGUOUS (no zigzag permutation), so RoPE uses plain offset positions.

Trade-off vs ring: Ulysses moves activations twice per layer in two
all-to-alls (volume ~4·b·s·d/n per device) regardless of causality, and
its parallelism is capped by the head count; ring moves k/v blocks n-1
times but overlaps transfers with compute and scales past the head count.
Both are first-class here: ``--cp-impl ulysses|ring``.

The reference has no sequence dimension at all (SURVEY.md §5.7) — this is
TPU-first long-context design, not parity.
"""

from __future__ import annotations

import jax
from jax import lax


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis: str, *, causal: bool = True,
                      attn_impl=None) -> jax.Array:
    """Attention under sequence sharding via head↔sequence all-to-alls.

    q: (batch, s_local, heads, hd); k/v may carry fewer (grouped-query)
    kv heads. Both head counts must be divisible by the ``axis`` size.
    Must run inside a shard_map region where ``axis`` is a manual axis and
    the inputs are sequence-sharded over it (callers: the context-parallel
    loss path, transformer.make_cp_loss_fn with cp_impl="ulysses").
    """
    if attn_impl is None:
        from tpudist.models.transformer import _attention
        attn_impl = _attention
    n = lax.axis_size(axis)
    for name, x in (("q heads", q.shape[2]), ("kv heads", k.shape[2])):
        if x % n:
            raise ValueError(
                f"ulysses needs {name} ({x}) divisible by the context "
                f"axis size ({n}); use --cp-impl ring when the head "
                f"count doesn't factor over the axis")

    def seq_to_heads(x):
        # (b, s/n, h, hd) -> (b, s, h/n, hd)
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    o = attn_impl(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                  causal=causal)
    # (b, s, h/n, hd) -> (b, s/n, h, hd)
    return lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)
