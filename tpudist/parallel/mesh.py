"""Device mesh construction.

The reference had no mesh concept — its only topology was "one process per
GPU, NCCL flat world" (reference ``slurm_train.sbatch:18-23``). TPU-first,
the mesh IS the parallelism config: a 6-axis ``jax.sharding.Mesh`` over
``('data', 'pipe', 'fsdp', 'expert', 'tensor', 'context')``. Axes of size 1
cost nothing, so every workload uses the same mesh shape and the same
PartitionSpecs — DP-only is just ``(n, 1, 1, 1, 1, 1)``.

Axis layout order matters on hardware: ``jax.make_mesh`` assigns the
fastest-varying (last) axes to the most tightly coupled devices, so axes are
ordered by communication intensity — tensor/context (per-layer collectives)
land on intra-host ICI neighbours, expert all-to-alls next, then fsdp
weight gathers; pipe (latency-tolerant point-to-point activations) and data
(one gradient all-reduce per step) cross DCN first.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from tpudist.config import ParallelConfig

# canonical axis order, most-global first
AXIS_NAMES: Tuple[str, ...] = ("data", "pipe", "fsdp", "expert", "tensor",
                               "context")


@dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    pipe: str = "pipe"
    fsdp: str = "fsdp"
    expert: str = "expert"
    tensor: str = "tensor"
    context: str = "context"


def resolve_axis_sizes(cfg: ParallelConfig, n_devices: int
                       ) -> Tuple[int, int, int, int, int, int]:
    """Resolve ``data=-1`` to "all remaining devices" and validate the
    factorisation (the topology-probe analogue of the reference CI's
    ``scontrol`` probe + sed patch, ci:115-119 — shapes are derived from the
    live device count, never hard-coded)."""
    fixed = cfg.pipe * cfg.fsdp * cfg.expert * cfg.tensor * cfg.context
    if fixed <= 0:
        raise ValueError(f"axis sizes must be >=1, got {cfg}")
    data = cfg.data
    if data == -1:
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by pipe*fsdp*expert*"
                f"tensor*context={fixed}")
        data = n_devices // fixed
    if data * fixed != n_devices:
        raise ValueError(
            f"mesh {data}x{cfg.pipe}x{cfg.fsdp}x{cfg.expert}x{cfg.tensor}"
            f"x{cfg.context} != {n_devices} devices")
    return (data, cfg.pipe, cfg.fsdp, cfg.expert, cfg.tensor, cfg.context)


def build_mesh(cfg: Optional[ParallelConfig] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    cfg = cfg or ParallelConfig()
    devices = list(devices) if devices is not None else jax.devices()
    sizes = resolve_axis_sizes(cfg, len(devices))
    if devices == jax.devices():
        # jax.make_mesh knows the physical topology: fastest-varying axes
        # land on ICI neighbours (a naive reshape of jax.devices() would
        # give no such guarantee and could put tensor-parallel collectives
        # on DCN). Axis types stay Auto: FSDP/TP rely on GSPMD propagation
        # (make_mesh defaults to Explicit, which type-rejects those layouts).
        auto = (jax.sharding.AxisType.Auto,) * len(AXIS_NAMES)
        return jax.make_mesh(sizes, AXIS_NAMES, axis_types=auto)
    import numpy as np
    return Mesh(np.asarray(devices).reshape(sizes), AXIS_NAMES)


def one_tpu_program() -> bool:
    """Can a Mosaic kernel, which GSPMD cannot partition, be called here
    as it is? On the TPU (the interpreter would crawl on the CPU, where
    each kernel's XLA path stays the tests' and its reference) and where
    the ambient mesh (``jax.set_mesh``) leaves nothing to partition: one
    device, no mesh, or every axis manual."""
    if jax.default_backend() != "tpu":
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return not (mesh.size > 1 and any(
        t == jax.sharding.AxisType.Auto for t in mesh.axis_types))


# ------------------------------------------------- slice / fabric layout
#
# Multi-slice awareness: a TPU pod of several slices exposes
# ``device.slice_index``; collectives whose mesh axis crosses slices
# ride DCN, everything else the ICI torus. CPU test meshes have no
# slices, so ``TPUDIST_SLICE_MAP`` scripts one — either an integer N
# ("split the devices into N equal contiguous slices by device id", the
# 2-slice DCN stand-in the overlap acceptance lane uses) or an explicit
# comma list of per-device slice indices. The scripted map changes only
# LABELING (axis_fabric -> "dcn", the comm_dcn grading threshold), never
# the compiled program: CPU collectives cannot be made to traverse a
# real DCN, but the attribution/grading plumbing is identical either
# way, which is exactly what makes it CI-testable.


def resolve_slice_map(n_devices: int) -> Optional[List[int]]:
    """``TPUDIST_SLICE_MAP`` -> per-device slice index for a full
    world of ids ``0..n_devices-1``, or None when unset. A thin list
    view over :func:`slice_assignment` — ONE parser of the env var —
    kept because "the whole world as a list" is the natural shape for
    tests and tooling. Malformed values raise: a scripted topology is
    an explicit test/bench request, not an advisory knob."""
    assigned = slice_assignment(range(n_devices))
    if assigned is None:
        return None
    return [assigned[i] for i in range(n_devices)]


def slice_assignment(devices) -> Optional[Dict[int, int]]:
    """The scripted slice of each of THESE devices (``{device_id:
    slice}``), or None when ``TPUDIST_SLICE_MAP`` is unset. The integer
    form splits the given devices' sorted ids into N contiguous runs —
    well-defined on a submesh (a 2-device test mesh of an 8-device
    world splits ITS devices) — while the explicit list form is global
    by device id and must cover every id present."""
    raw = os.environ.get("TPUDIST_SLICE_MAP")
    if not raw:
        return None
    vals = [int(p) for p in raw.split(",") if p.strip()]
    ids = sorted(int(getattr(d, "id", i))
                 for i, d in enumerate(devices))
    if len(vals) == 1:
        n_slices = vals[0]
        if n_slices < 1 or len(ids) % n_slices:
            raise ValueError(
                f"TPUDIST_SLICE_MAP={raw!r}: {len(ids)} devices not "
                f"divisible into {n_slices} equal slices")
        per = len(ids) // n_slices
        return {d: i // per for i, d in enumerate(ids)}
    for d in ids:
        if d < 0 or d >= len(vals):
            raise ValueError(
                f"TPUDIST_SLICE_MAP={raw!r}: {len(vals)} entries do "
                f"not cover device id {d}")
    return {d: vals[d] for d in ids}


def device_slice_index(device,
                       scripted: Optional[Dict[int, int]] = None) -> int:
    """One device's slice: the scripted map (by device id) wins, else
    the runtime's ``slice_index`` attribute, else 0 (single slice)."""
    if scripted is not None:
        did = int(getattr(device, "id", 0))
        if did in scripted:
            return scripted[did]
    return int(getattr(device, "slice_index", 0) or 0)


def axis_fabric(mesh: Mesh, axis: str) -> str:
    """Label a mesh axis ``ici`` or ``dcn`` from the devices it spans.

    An axis whose neighbouring devices sit on different SLICES crosses
    the data-center network; within one slice it rides the ICI torus.
    The probe walks the mesh's device array: fix every other axis and
    look at the set of slice indices along this one — more than one
    distinct slice anywhere ⇒ DCN. Devices without a slice (CPU without
    a scripted ``TPUDIST_SLICE_MAP``, single-slice TPU runtimes) read
    as one slice, i.e. ICI — exactly the bandwidth class their
    collective actually gets. (Moved here from tpudist.bench.sweep: the
    fabric of an axis is a MESH property, consumed by the sweep's
    artifact rows, the devtime comm grading, and the overlap bench.)"""
    import numpy as np
    devs = mesh.devices
    scripted = slice_assignment(devs.ravel())
    idx = list(mesh.axis_names).index(axis)
    cols = np.moveaxis(devs, idx, 0).reshape(devs.shape[idx], -1)
    for j in range(cols.shape[1]):
        slices = {device_slice_index(d, scripted) for d in cols[:, j]}
        if len(slices) > 1:
            return "dcn"
    return "ici"


def axis_hops(mesh: Mesh, axis: str) -> List[str]:
    """Per-hop fabric along a mesh axis: entry ``i`` labels the edge
    from axis position ``i`` to ``(i+1) % size`` (the last entry is the
    ring wrap hop, which is what a ``ppermute`` ring actually pays).

    :func:`axis_fabric` collapses the whole axis to ``dcn`` if ANY hop
    crosses slices — correct for a fused all-reduce (one collective
    rides the slowest link it touches) but too coarse for point-to-point
    schedules: a pipeline whose stages straddle two slices crosses DCN
    on exactly one interior hop (plus the wrap) while every other hop
    stays on ICI. The per-hop view lets the DCN-bytes accounting and
    the MPMD stage plan price mixed axes exactly. A hop is ``dcn`` when
    any pair of devices it connects (over all positions of the other
    axes) sits on different slices."""
    import numpy as np
    devs = mesh.devices
    scripted = slice_assignment(devs.ravel())
    idx = list(mesh.axis_names).index(axis)
    cols = np.moveaxis(devs, idx, 0).reshape(devs.shape[idx], -1)
    size = cols.shape[0]
    hops: List[str] = []
    for i in range(size):
        j = (i + 1) % size
        crossed = any(
            device_slice_index(cols[i, c], scripted)
            != device_slice_index(cols[j, c], scripted)
            for c in range(cols.shape[1]))
        hops.append("dcn" if crossed else "ici")
    return hops


def mesh_fabrics(mesh: Mesh) -> Dict[str, str]:
    """Every size->1 axis's fabric label — the ``axis_fabric`` map the
    devtime record and the run report carry (axes of size 1 have no
    collective to label)."""
    return {axis: axis_fabric(mesh, axis)
            for axis in mesh.axis_names if mesh.shape[axis] > 1}


def data_fabric(mesh: Mesh) -> str:
    """The DP gradient all-reduce's fabric: the ``data`` axis label
    when that axis is real, else ICI (no cross-device reduce at all)."""
    if mesh.shape.get("data", 1) > 1:
        return axis_fabric(mesh, "data")
    return "ici"


def mesh_device_slices(mesh: Mesh) -> List[int]:
    """Slice index of every mesh device in FLAT (C-order) mesh
    position. This is the id space a lowered program's
    ``replica_groups`` / ``source_target_pairs`` index into
    (``use_global_device_ids`` numbers devices by their position in
    the computation's device assignment, which jit takes from the
    mesh), so it is the slice table obs.devtime's collective byte
    accounting consumes."""
    devs = list(mesh.devices.ravel())
    scripted = slice_assignment(devs)
    return [device_slice_index(d, scripted) for d in devs]


@dataclass(frozen=True)
class SliceGroups:
    """The slice structure of the ``data`` axis, as collective
    subgroups: ``in_slice[s]`` holds the data-axis indices of slice
    ``s``'s members (the ICI reduce-scatter / all-gather groups),
    ``cross_slice[j]`` holds the ``j``-th member of every slice (the
    DCN all-reduce groups — each moves a 1/``slice_size`` shard in the
    hierarchical schedule). Groups are ``axis_index_groups`` for
    collectives over the ``data`` axis inside the pure-DP shard_map,
    where axis index == mesh position."""

    n_slices: int
    slice_size: int
    in_slice: Tuple[Tuple[int, ...], ...]
    cross_slice: Tuple[Tuple[int, ...], ...]


def data_slice_groups(mesh: Mesh) -> Optional[SliceGroups]:
    """The data axis's :class:`SliceGroups`, or None when there is no
    slice structure to exploit (data axis of size 1, or every data
    position on one slice — the single-slice downgrade case).

    Raises when a single data position spans slices (a non-DP mesh
    whose other axes straddle a slice boundary — in-slice/cross-slice
    grouping is undefined there) and when slices are unequal (the
    1/slice_size shard layout needs one shard per in-slice member in
    every slice; an irregular scripted map is a config error, not a
    degraded mode)."""
    import numpy as np
    n = mesh.shape.get("data", 1)
    if n <= 1:
        return None
    devs = mesh.devices
    scripted = slice_assignment(devs.ravel())
    idx = list(mesh.axis_names).index("data")
    cols = np.moveaxis(devs, idx, 0).reshape(n, -1)
    pos_slice: List[int] = []
    for i in range(n):
        seen = {device_slice_index(d, scripted) for d in cols[i]}
        if len(seen) > 1:
            raise ValueError(
                f"data position {i} spans slices {sorted(seen)}: "
                f"in-slice/cross-slice grouping needs every data-axis "
                f"position on ONE slice")
        pos_slice.append(seen.pop())
    by_slice: Dict[int, List[int]] = {}
    for i, s in enumerate(pos_slice):
        by_slice.setdefault(s, []).append(i)
    if len(by_slice) == 1:
        return None
    groups = [tuple(v) for _, v in sorted(by_slice.items())]
    sizes = {len(g) for g in groups}
    if len(sizes) > 1:
        raise ValueError(
            f"unequal slice sizes {sorted(len(g) for g in groups)} on "
            f"the data axis: the hierarchical schedule shards each "
            f"reduce 1/slice_size and needs equal slices")
    per = sizes.pop()
    cross = tuple(tuple(g[j] for g in groups) for j in range(per))
    return SliceGroups(n_slices=len(groups), slice_size=per,
                       in_slice=tuple(groups), cross_slice=cross)
