"""Measured collectives: the fabric-acceptance core.

The reference exercised its collective stack (NCCL all-reduce) implicitly
inside DeepSpeed and never measured it (SURVEY.md §5.8, §6). Here the
collective layer is a first-class, *measured* component: explicit shard_map
wrappers around the XLA collectives plus correct bus-bandwidth accounting —
the BASELINE.json headline metric is ≥90% of ICI peak all-reduce bus
bandwidth on a real slice.

Bus-bandwidth convention (nccl-tests / ring-algorithm):
    reported size S = the logical message (see each kind below)
    all_reduce      busBW = 2(n-1)/n × S / t
    all_gather      busBW =  (n-1)/n × S / t   (S = full gathered buffer)
    reduce_scatter  busBW =  (n-1)/n × S / t   (S = full input buffer)
    all_to_all      busBW =  (n-1)/n × S / t   (S = per-rank send buffer)
    ppermute        busBW =            S / t   (S = per-hop message; pure
                                                point-to-point ICI probe)

Every input is laid out so each device holds DISTINCT data — a replicated
input could legally be constant-folded by XLA (psum of known-replicated x
is just n·x), which would time nothing (the fusion hazard in SURVEY.md §7).
shard_map pins the collective in the program.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

KINDS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
         "ppermute")

BUS_FACTOR: Dict[str, Callable[[int], float]] = {
    "all_reduce": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
}


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def build_op(kind: str, mesh: Mesh, axis: str, *, message_bytes: int,
             dtype=jnp.float32) -> Tuple[Callable, jax.Array, int]:
    """Build (jitted op, input array, actual message bytes) for one
    collective at one message size.

    ``message_bytes`` is the logical message size S per the convention in
    the module docstring; rounded down so shapes tile evenly over the axis.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown collective {kind!r}; one of {KINDS}")
    n = mesh.shape[axis]
    item = jnp.dtype(dtype).itemsize
    elems = max(message_bytes // item, n)
    elems = (elems // n) * n

    def _sharded_iota(total, spec, shape=None):
        """Generate the input directly in its sharded layout — each device
        materialises only its own shard (a host-side arange would land on
        one device first and OOM at GB sizes × slice width)."""
        def gen():
            v = jnp.arange(total, dtype=dtype)
            return v.reshape(shape) if shape else v
        return jax.jit(gen, out_shardings=NamedSharding(mesh, spec))()

    if kind in ("all_reduce", "reduce_scatter"):
        # each device holds a DISTINCT full buffer: global (n, E), P(axis)
        x = _sharded_iota(n * elems, P(axis, None), shape=(n, elems))

        if kind == "all_reduce":
            def body(v):
                return lax.psum(v[0], axis)
            out_spec = P(None)
        else:
            def body(v):
                return lax.psum_scatter(v[0], axis, tiled=True)
            out_spec = P(axis)
        fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis, None),
                           out_specs=out_spec, check_vma=False)
    elif kind == "all_gather":
        # shards of E/n gather into the full E buffer on every device
        x = _sharded_iota(elems, P(axis))

        def body(v):
            return lax.all_gather(v, axis, tiled=True)
        fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                           out_specs=P(None), check_vma=False)
    elif kind == "all_to_all":
        # each device's send buffer is E (global n·E), exchanged n-ways
        x = _sharded_iota(n * elems, P(axis))

        def body(v):
            return lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)
    else:  # ppermute: each device passes its E-buffer one hop around the ring
        x = _sharded_iota(n * elems, P(axis))

        def body(v):
            return lax.ppermute(v, axis, perm=_ring_perm(n))
        fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)

    return jax.jit(fn), x, elems * item


@dataclass
class CollectiveTiming:
    kind: str
    n_devices: int
    message_bytes: int
    mean_s: float
    min_s: float
    algo_gbps: float       # message_bytes / min_s
    bus_gbps: float        # algo × bus factor


def time_collective(kind: str, mesh: Mesh, axis: str, *,
                    message_bytes: int, dtype=jnp.float32,
                    iters: int = 10, warmup: int = 3) -> CollectiveTiming:
    """Time one collective at one message size with block_until_ready
    fencing; warmup reps absorb compile + first-touch."""
    n = mesh.shape[axis]
    op, x, actual_bytes = build_op(kind, mesh, axis,
                                   message_bytes=message_bytes, dtype=dtype)
    for _ in range(warmup):
        jax.block_until_ready(op(x))
    times: List[float] = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(op(x))
        times.append(time.perf_counter() - t0)
    mean_s = sum(times) / len(times)
    min_s = min(times)
    algo = actual_bytes / min_s / 1e9
    return CollectiveTiming(kind=kind, n_devices=n,
                            message_bytes=actual_bytes, mean_s=mean_s,
                            min_s=min_s, algo_gbps=algo,
                            bus_gbps=algo * BUS_FACTOR[kind](n))
