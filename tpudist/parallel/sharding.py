"""Sharding helpers: NamedShardings for params/batches/opt-state.

The DeepSpeed-engine analogue of "ZeRO stage N" lives here as data, not
code: FSDP (~ZeRO-3 for params+grads+opt state) is just a PartitionSpec per
weight (models' ``param_specs``); XLA's SPMD partitioner inserts the
all-gathers/reduce-scatters that DeepSpeed implements by hand
(reference consumed that via ``deepspeed.initialize``, train.py:87-93).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def sanitize_specs(shape_tree, spec_tree, mesh: Mesh):
    """Drop sharding axes that don't divide the corresponding dim evenly
    (e.g. a vocab of 97 over fsdp=2): those dims fall back to replicated,
    which is always legal. Tuple axes keep their longest dividing PREFIX
    (r4 review: vocab 1000 over (fsdp=8, tensor=4) must stay 8-way
    fsdp-sharded, not fall all the way back to replicating the biggest
    tensor). Keeps model PartitionSpecs mesh-agnostic."""
    def fix(shape, spec):
        dims = list(spec) + [None] * (len(shape.shape) - len(spec))
        out = []
        for size, axes in zip(shape.shape, dims):
            if axes is None:
                out.append(None)
                continue
            axes_t = axes if isinstance(axes, tuple) else (axes,)
            kept = []
            ways = 1
            for a in axes_t:
                if size % (ways * mesh.shape[a]):
                    break
                kept.append(a)
                ways *= mesh.shape[a]
            out.append(tuple(kept) if len(kept) > 1
                       else (kept[0] if kept else None))
        return P(*out)
    return jax.tree.map(fix, shape_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def named(mesh: Mesh, spec_tree):
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def pure_dp(mesh: Mesh) -> bool:
    """True when only the ``data`` axis is > 1 — the explicit-collective
    DP shard_map engine path (engine._build_step_body), and therefore
    the mesh whose gradient all-reduce the overlap plane
    (parallel.overlap, ``--grad-overlap``) can schedule. One predicate,
    shared by the engine's path choice and the tuner's axis gating, so
    the two cannot disagree about which program a config dispatches."""
    return (mesh.shape.get("data", 1) > 1
            and all(mesh.shape.get(a, 1) == 1
                    for a in ("pipe", "fsdp", "expert", "tensor",
                              "context")))


def batch_spec(ndim: int) -> P:
    """Batch arrays shard their leading (batch) dim over data AND fsdp axes —
    fsdp replicas are extra data-parallel workers for activations."""
    return P(("data", "fsdp"), *([None] * (ndim - 1)))


def epoch_spec(ndim: int) -> P:
    """Spec for epoch/superstep slabs shaped ``(steps, local_batch, ...)``:
    dim 0 is the step axis (unsharded — every device sees the full step
    range; ``lax.scan`` consumes it), the batch dim rides data+fsdp as in
    :func:`batch_spec`."""
    return P(None, ("data", "fsdp"), *([None] * (ndim - 2)))


def put_epoch(mesh: Mesh, batches):
    """Stage ``(steps, local_batch, ...)`` arrays — a whole epoch or one
    :class:`SlabPlan` slab — into device memory (HBM on TPU), sharded
    batch-wise per :func:`epoch_spec`.

    One async host→device transfer per slab replaces a per-step
    ``put_batch``: ``device_put`` returns immediately, so the transfer
    overlaps whatever compute is already enqueued (the previous slab's
    supersteps in the streaming loop), and every superstep's k-slice is
    then an on-device slice — no host fence on the hot path.
    Multi-process follows :func:`put_batch`'s contract: each host owns a
    distinct batch-dim slice of every global step.
    """
    import numpy as np

    def _put(x):
        sh = NamedSharding(mesh, epoch_spec(np.ndim(x)))
        if jax.process_count() == 1:
            return jax.device_put(x, sh)
        return jax.make_array_from_process_local_data(sh, np.asarray(x))
    return jax.tree.map(_put, batches)


@dataclasses.dataclass(frozen=True)
class SlabPlan:
    """How one epoch's batches move host→device under the staging budget.

    ``slab_steps`` is the staging granularity: the train loop materialises
    and ``device_put``s one ``(slab_steps, local_batch, ...)`` slab while
    the previous slab's supersteps run — double-buffered, so at most two
    slabs are resident and ``2 * slab_bytes <= budget_bytes`` by
    construction. The fast path (``streamed=False``) is the degenerate
    one-slab plan: the whole epoch (padded to a ``k``-multiple) stages in
    one async transfer, exactly PR 1's behavior.
    """

    n_steps: int            # true steps in the epoch
    k: int                  # superstep length (steps per compiled dispatch)
    slab_steps: int         # steps per staged slab (a k-multiple)
    n_slabs: int
    step_bytes: int         # per-device bytes of one step's batch
    budget_bytes: Optional[int]
    streamed: bool

    @property
    def slab_bytes(self) -> int:
        return self.slab_steps * self.step_bytes


def plan_slabs(n_steps: int, k: int, step_bytes: int,
               budget_bytes: Optional[int]) -> SlabPlan:
    """Cut an epoch into double-buffered staging slabs under
    ``budget_bytes`` of per-device staging memory.

    * epoch fits the budget (or no budget) → the full-epoch fast path:
      one slab, ``streamed=False``.
    * otherwise → the largest ``k``-multiple slab with two copies inside
      the budget (current + in-flight next).
    * budget too small to double-buffer even one ``k``-step slab → a
      clear config error, not a silent OOM at dispatch time.
    """
    if n_steps < 1:
        raise ValueError(f"epoch must have >= 1 step, got {n_steps}")
    if k < 1:
        raise ValueError(f"superstep length must be >= 1, got {k}")
    step_bytes = max(int(step_bytes), 1)
    padded = -(-n_steps // k) * k
    # the fast path stages the PADDED epoch, so the fit check must use
    # it too — an epoch just under budget must stream, not stage k-1
    # extra padded steps past the budget
    if budget_bytes is None or padded * step_bytes <= budget_bytes:
        return SlabPlan(n_steps, k, padded, 1, step_bytes, budget_bytes,
                        streamed=False)
    slab_steps = (budget_bytes // 2) // step_bytes // k * k
    if slab_steps < k:
        need = 2 * k * step_bytes
        raise ValueError(
            f"staging budget {budget_bytes / 2**20:.2f} MB cannot hold a "
            f"double-buffered pair of k={k}-step slabs "
            f"({need / 2**20:.2f} MB needed at "
            f"{step_bytes / 2**20:.3f} MB/step): raise --staging-budget-mb "
            f"or lower --steps-per-dispatch")
    slab_steps = min(slab_steps, padded)
    n_slabs = -(-padded // slab_steps)
    return SlabPlan(n_steps, k, slab_steps, n_slabs, step_bytes,
                    budget_bytes, streamed=True)


def paged_kv_cache_specs() -> P:
    """``param_specs``-style PartitionSpec for the serving KV pool
    ``(layers, kv_heads, pages+1, page_tokens, head_dim)``
    (tpudist.serve.kvcache); one spec serves both the K and V arrays.
    Pages — the pool's embarrassingly-parallel dim — ride the batch axes
    like every activation (:func:`batch_spec`), kv heads ride tensor
    (the same Megatron head split the attention weights use), and the
    layer / in-page-position / head_dim dims stay unsharded. Compose with
    :func:`sanitize_specs` so a pool size the batch axes don't divide
    falls back to replicated instead of erroring (the +1 trash page
    makes odd pool sizes the COMMON case, not the exception)."""
    return P(None, "tensor", ("data", "fsdp"), None, None)


def norm_shard_index(idx, shape) -> tuple:
    """A sharding index (tuple of slices, as produced by
    ``Sharding.devices_indices_map`` / ``Shard.index``) normalised to
    concrete per-dim ``(start, stop)`` pairs — hashable, json-able, and
    mesh-agnostic, which is what lets the elastic checkpoint layout
    (tpudist.elastic.ckpt) describe a shard independently of the mesh
    that produced it."""
    out = []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def owned_shard_spans(leaf, process_index: int):
    """The distinct shards of ``leaf`` that process ``process_index``
    OWNS for writing: its addressable shards, deduped by slice span,
    minus any span also held by a lower-ranked process — a replicated
    leaf is written exactly once pod-wide, by the lowest owner (pure-DP
    params must not cost process_count copies on disk). Returns
    ``[(span, shard_data), ...]`` with span per :func:`norm_shard_index`.
    Host-side leaves with no sharding are treated as replicated."""
    import numpy as np

    sharding = getattr(leaf, "sharding", None)
    shape = tuple(getattr(leaf, "shape", ()))
    if sharding is None or not hasattr(leaf, "addressable_shards"):
        if process_index != 0:
            return []
        return [(tuple((0, d) for d in shape), np.asarray(leaf))]
    owner: dict = {}
    for dev, idx in sharding.devices_indices_map(shape).items():
        span = norm_shard_index(idx, shape)
        p = int(getattr(dev, "process_index", 0))
        owner[span] = min(owner.get(span, p), p)
    out, seen = [], set()
    for sh in leaf.addressable_shards:
        span = norm_shard_index(sh.index, shape)
        if span in seen or owner.get(span) != process_index:
            continue
        seen.add(span)
        out.append((span, np.asarray(sh.data)))
    return out


def batch_sharding(mesh: Mesh, tree):
    return jax.tree.map(
        lambda x: NamedSharding(mesh, batch_spec(x.ndim)), tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def put_params(mesh: Mesh, params, spec_tree):
    """Device-put a params pytree to its FSDP/TP layout."""
    return jax.device_put(params, named(mesh, spec_tree))


def put_batch(mesh: Mesh, batch):
    """Shard host-local batch arrays onto the mesh's batch axes.

    Single-process: a plain device_put with the sharding (no copy if already
    placed). Multi-process: each host owns a DISTINCT slice of the global
    batch (tpudist.data.shard_epoch's contract), assembled into a global
    array via ``make_array_from_process_local_data`` — a plain device_put
    would wrongly treat each host's local shard as the whole batch.
    """
    import numpy as np

    def _put(x):
        sh = NamedSharding(mesh, batch_spec(np.ndim(x)))
        if jax.process_count() == 1:
            return jax.device_put(x, sh)
        return jax.make_array_from_process_local_data(sh, np.asarray(x))
    return jax.tree.map(_put, batch)
