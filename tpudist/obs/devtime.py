"""Device-time attribution: compute vs exposed communication.

Everything the observability stack records so far is HOST wall-clock:
the tracer's ``fence`` span lumps device compute, collective time, and
straggler wait into one number, so "exposed-communication time per
phase must drop" (ROADMAP item 3's acceptance signal) cannot be
measured. This module is the first layer that sees what the CHIP did:

  * a **jax-free parser** for the Chrome trace-event JSON that
    ``jax.profiler`` already writes per worker
    (``plugins/profile/*/*.trace.json.gz`` — stdlib ``gzip`` + ``json``,
    no TensorBoard, no xprof): device-timeline ops are classified into
    compute vs collective communication by HLO op name;
  * **interval math** that computes *exposed* communication — comm time
    NOT overlapped by compute on the same device track — by interval
    subtraction. The decomposition is exact and mutually exclusive:
    ``compute + exposed_comm + idle == window`` per device (comm that
    overlaps compute is *hidden* and counts as compute time, which is
    precisely what overlap optimisations buy);
  * a :class:`WindowProfiler` capture mode (``--profile-window N`` /
    ``TPUDIST_PROFILE_WINDOW``): N mid-run supersteps captured on every
    worker into ``profile/worker<i>`` and ingested automatically at run
    end — cheap enough to leave on for acceptance runs, unlike the
    full-run ``--profile-dir`` which stays a manual debug tool (and is
    the only capture mode that still disables autotuning);
  * the three consumers: a ``kind=devtime`` metrics record, device
    tracks merged under each host's row in ``pod_trace.json`` (the
    capture's timestamps share ``perf_counter``'s timebase, so PR 5's
    clock-offset machinery aligns them across hosts for free), and the
    run report's "Device time" section with per-phase exposed-comm
    attribution and a ``comm_status`` verdict
    (``TPUDIST_COMM_EXPOSED_MAX``).

The parser half of this module MUST stay importable without jax —
``tpudist.obs.report`` runs on a laptop against scp'd artifacts. All
jax use lives inside :class:`WindowProfiler` methods (lazy imports).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

# tpudist.verdict is import-safe on the jax-free offline path (its jax
# uses are lazy), so the status vocabulary has one home
from tpudist import rules as rules_lib
from tpudist.scopes import layer_of, scope_path
from tpudist.verdict import FAIL, SUCCESS, UNGATEABLE

Interval = Tuple[float, float]

# ------------------------------------------------------- classification

# Collective-communication HLO ops (async -start/-done variants and
# fusions embedding them match too): the names XLA gives the device
# timeline on TPU ("all-reduce.3"), GPU ("ncclAllReduce...") and the
# CPU thunk runtime ("all-reduce.1"). "megascale" covers the TPU
# multi-slice DCN transfer ops.
_COMM_RE = re.compile(
    r"(?:^|[^a-z])(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|ragged-all-to-all|"
    r"send|recv|megascale|nccl)", re.IGNORECASE)

# Runtime/infra timeline entries that are neither compute nor comm:
# C++ scopes ("ThunkExecutor::Execute"), the profiler's python tracer
# ("$builtins isinstance"), and dispatch bookkeeping. An HLO op name is
# a bare identifier — letters/digits/_/-/. only.
_OP_NAME_RE = re.compile(r"^[A-Za-z][A-Za-z0-9_.\-]*$")


def classify(name: str) -> Optional[str]:
    """``"comm"`` / ``"compute"`` for device ops, ``None`` for runtime
    noise that must not count toward device busy time."""
    if not name or not _OP_NAME_RE.match(name):
        return None
    return "comm" if _COMM_RE.search(name) else "compute"


# ------------------------------------- program-derived collective bytes
#
# CPU wall-clock cannot honestly measure a DCN-byte win (PR 12's
# observer-effect lesson), but the LOWERED PROGRAM states it exactly:
# every collective op carries its payload tensor type and its replica
# groups, and the mesh knows which device ids share a slice. Parsing
# the StableHLO text (engine's ``.lowered_text()`` hook) therefore
# yields per-collective byte volumes per fabric as program facts — the
# hierarchical schedule's bytes-over-DCN cut is asserted from these
# rows, never from timing. Stays jax-free like the rest of the parser
# half: tests and the offline report feed it saved text.

# StableHLO collective ops (MLIR spelling — underscores, unlike the
# device-timeline HLO names above).
_COLLECTIVE_OPS = ("all_reduce", "reduce_scatter", "all_gather",
                   "all_to_all", "collective_permute",
                   "collective_broadcast")
_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(" + "|".join(_COLLECTIVE_OPS) + r")\b")
_DENSE_RE = {
    attr: re.compile(attr + r"\s*=\s*dense<(.*?)>\s*:\s*tensor<([0-9x]*)",
                     re.DOTALL)
    for attr in ("replica_groups", "source_target_pairs")
}
# an op's type signature: "(operands) -> result" — on the op's own line
# for region-free ops, on the "}) : (...)" closing line for the
# region-carrying reduces
_SIG_RE = re.compile(r":\s*\(([^()]*)\)\s*->\s*\(?\s*(tensor<[^>]*>)")
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")

_MLIR_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3": 1, "f8E3M4": 1,
}


def _tensor_bytes(ty: str) -> Tuple[int, str]:
    """``"2x11xf32"`` -> (88, "f32"); scalar ``"f32"`` -> (4, "f32")."""
    parts = ty.strip().split("x")
    dtype = parts[-1]
    n = 1
    for p in parts[:-1]:
        n *= int(p)
    return n * _MLIR_DTYPE_BYTES.get(dtype, 4), dtype


def _parse_dense(attr: str, window: str) -> Optional[List[List[int]]]:
    """An MLIR dense int attribute -> list of rows. Handles the
    explicit nested-list form and the splat form (``dense<0>`` with
    the row shape taken from the tensor type)."""
    m = _DENSE_RE[attr].search(window)
    if not m:
        return None
    body, shape = m.group(1).strip(), m.group(2)
    dims = [int(d) for d in shape.split("x") if d]
    if body.startswith("["):
        rows = re.findall(r"\[([^\[\]]*)\]", body)
        return [[int(v) for v in r.split(",") if v.strip()] for r in rows]
    # splat: one value repeated over the whole shape
    v = int(body)
    n_rows = dims[0] if dims else 1
    n_cols = dims[1] if len(dims) > 1 else 1
    return [[v] * n_cols for _ in range(n_rows)]


def collective_bytes(text: str, device_slices: Sequence[int]
                     ) -> Dict[str, Any]:
    """Per-collective byte accounting of a lowered StableHLO module.

    ``device_slices[i]`` is the slice of device id ``i`` in the
    program's device assignment (``mesh.mesh_device_slices`` — the id
    space ``replica_groups``/``source_target_pairs`` index into).

    Returns ``{"ops": [row...], "dcn_bytes_total", "ici_bytes_total",
    "n_collectives"}``. Each row aggregates identical ops: ``op``,
    ``dtype``, ``bytes`` (payload per instance — the larger of operand
    and result tensors, i.e. the full vector a reduce-scatter consumes
    or an all-gather produces), ``count``, ``fabric`` (``dcn`` when any
    replica group spans slices, ``mixed`` for a permute with both kinds
    of edge), and ``dcn_bytes`` (total over ``count``: payload × the
    number of participants whose traffic crosses slices — for group
    collectives every member of a slice-spanning group, for a permute
    each slice-crossing source→target edge). The convention prices a
    participant's payload once per instance, so the hierarchical ladder's
    cross-slice all-reduce (1/slice_size shard, all N participants)
    lands at exactly 1/slice_size of the flat schedule's — the relation
    the acceptance tests pin. A ``lax.scan`` body lowers once, so rows
    approximate per-step volumes regardless of superstep length."""
    slices = list(device_slices)
    agg: Dict[tuple, Dict[str, Any]] = {}
    lines = text.splitlines()
    for i, line in enumerate(lines):
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        sig = _SIG_RE.search(line)
        j = i
        while sig is None and j + 1 < len(lines) and j - i < 50:
            # region-carrying op (all_reduce / reduce_scatter): the
            # type signature lives on the "}) : (...)" closing line
            j += 1
            if "}) :" in lines[j]:
                sig = _SIG_RE.search(lines[j])
                break
        if sig is None:
            continue
        operand_tys = _TENSOR_RE.findall(sig.group(1))
        result_ty = _TENSOR_RE.search(sig.group(2))
        tys = operand_tys + ([result_ty.group(1)] if result_ty else [])
        if not tys:
            continue
        sized = [_tensor_bytes(t) for t in tys]
        payload, dtype = max(sized, key=lambda s: s[0])
        groups = _parse_dense("replica_groups", line)
        pairs = _parse_dense("source_target_pairs", line)
        fabric = "ici"
        dcn_participants = 0
        if pairs is not None:
            crossing = sum(1 for p in pairs if len(p) == 2
                           and _crosses(p, slices))
            dcn_participants = crossing
            if crossing == len(pairs) and pairs:
                fabric = "dcn"
            elif crossing:
                fabric = "mixed"
        elif groups is not None:
            for g in groups:
                if _crosses(g, slices):
                    dcn_participants += len(g)
            if dcn_participants:
                fabric = "dcn"
        key = (op, dtype, payload, fabric, dcn_participants)
        row = agg.setdefault(key, {
            "op": op, "dtype": dtype, "bytes": payload, "count": 0,
            "fabric": fabric, "dcn_bytes": 0})
        row["count"] += 1
        row["dcn_bytes"] += payload * dcn_participants
    ops = sorted(agg.values(),
                 key=lambda r: (-r["dcn_bytes"], -r["bytes"], r["op"]))
    dcn_total = sum(r["dcn_bytes"] for r in ops)
    ici_total = sum(r["bytes"] * r["count"] for r in ops
                    if r["fabric"] == "ici")
    return {"ops": ops, "dcn_bytes_total": dcn_total,
            "ici_bytes_total": ici_total,
            "n_collectives": sum(r["count"] for r in ops)}


def _crosses(ids: Sequence[int], slices: List[int]) -> bool:
    """True when the id group spans more than one slice (out-of-range
    ids — a program lowered for a larger world than the slice table —
    read as slice 0, the conservative single-slice answer)."""
    seen = set()
    for d in ids:
        seen.add(slices[d] if 0 <= d < len(slices) else 0)
        if len(seen) > 1:
            return True
    return False


# -------------------------------------------------------- interval math


def merge_intervals(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` (zero-length dropped)."""
    ivs = sorted((lo, hi) for lo, hi in intervals if hi > lo)
    out: List[Interval] = []
    for lo, hi in ivs:
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def measure(intervals: Sequence[Interval]) -> float:
    """Total length of a DISJOINT interval list."""
    return sum(hi - lo for lo, hi in intervals)


def subtract_intervals(a: Sequence[Interval],
                       b: Sequence[Interval]) -> List[Interval]:
    """``a \\ b`` — the parts of ``a`` not covered by ``b`` (both are
    union-normalised first). This IS the exposed-communication
    operator: ``subtract(comm, compute)``."""
    a = merge_intervals(a)
    b = merge_intervals(b)
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            blo, bhi = b[k]
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def intersect_intervals(a: Sequence[Interval],
                        b: Sequence[Interval]) -> List[Interval]:
    """``a ∩ b`` (union-normalised)."""
    a = merge_intervals(a)
    b = merge_intervals(b)
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ------------------------------------------------------ capture parsing


def find_captures(capture_dir: str) -> List[str]:
    """The trace-event JSON files under a ``jax.profiler`` capture dir
    (``plugins/profile/<session>/<host>.trace.json.gz``)."""
    pats = (os.path.join(capture_dir, "**", "*.trace.json.gz"),
            os.path.join(capture_dir, "**", "*.trace.json"))
    out: List[str] = []
    for p in pats:
        out.extend(glob.glob(p, recursive=True))
    return sorted(out)


def load_capture_doc(path: str) -> Dict[str, Any]:
    """One capture file → the Chrome trace-event document (stdlib gzip +
    json; no protobuf, no TensorBoard)."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return json.load(f)
    with open(path) as f:
        return json.load(f)


def device_op_tracks(doc: Dict[str, Any]
                     ) -> Dict[str, List[Tuple[float, float, str]]]:
    """Device-timeline op intervals per device track:
    ``{device_name: [(t0_us, t1_us, op_name), ...]}``.

    On TPU/GPU the profiler emits one PROCESS per device
    (``/device:TPU:0``) whose "XLA Ops" thread carries the op events —
    each such pid is one track. The CPU backend has no device
    processes; its op events land on the PJRT client's pool threads
    (``tf_XLATfrtCpuClient/*`` — and, under the thunk runtime newer
    jaxlibs use, the Eigen compute pool ``tf_XLAEigen/*``, where the
    HLO op events actually live) inside the ``/host:CPU`` process, so
    all of them fold into ONE synthetic track per host process (the
    virtual devices share the pool — per-device attribution is a
    hardware concept; the CPU track exists so the plumbing is testable
    end-to-end without a TPU).
    """
    proc_names: Dict[Any, str] = {}
    thread_names: Dict[Tuple[Any, Any], str] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")

    device_pids = {pid: name.split("/device:", 1)[1]
                   for pid, name in proc_names.items()
                   if name.startswith("/device:")}
    # device pids with an "XLA Ops" thread: only those threads are op
    # executions (the "Steps"/"XLA Modules" threads carry step numbers
    # and whole-module windows that would double-count)
    xla_ops_pids = {pid for (pid, tid), tn in thread_names.items()
                    if pid in device_pids and "XLA Ops" in tn}

    tracks: Dict[str, List[Tuple[float, float, str]]] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        pid, tid = e.get("pid"), e.get("tid")
        name = e.get("name", "")
        if pid in device_pids:
            tn = thread_names.get((pid, tid), "")
            if pid in xla_ops_pids and "XLA Ops" not in tn:
                continue
            if classify(name) is None:
                continue
            track = device_pids[pid]
        else:
            tn = thread_names.get((pid, tid), "")
            if not tn.startswith(("tf_XLATfrtCpuClient", "tf_XLAEigen")):
                continue
            if classify(name) is None:
                continue
            track = proc_names.get(pid, "/host:CPU").lstrip("/") or "host"
        t0 = float(e["ts"])
        tracks.setdefault(track, []).append((t0, t0 + float(e["dur"]),
                                             name))
    return tracks


def scope_seconds(doc: Dict[str, Any]) -> Dict[str, float]:
    """Device SECONDS per layer (``tpudist.scopes.layer_of`` of each
    op's scope path), summed over the device tracks of one capture
    document; time whose op carries none of the program's scopes goes
    under ``""``.

    The scope path travels in each ``XLA Ops`` event's ``tf_op``
    argument (the op's name stack: where a v5e capture of jaxlib 0.9.0
    carries it). Only LEAF events count: a ``while`` or ``cond`` on the
    op line spans the ops of its body, and is told from them by
    containing another event's start, not by its name. An op inside a
    scoped ``while`` body whose own metadata the compiler dropped takes
    the scope of the nearest container that has one."""
    thread_names: Dict[Tuple[Any, Any], str] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    per_thread: Dict[Tuple[Any, Any], list] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        key = (e.get("pid"), e.get("tid"))
        if thread_names.get(key) != "XLA Ops":
            continue
        t0 = float(e["ts"])
        per_thread.setdefault(key, []).append(
            (t0, t0 + float(e["dur"]),
             scope_path((e.get("args") or {}).get("tf_op"))))
    out: Dict[str, float] = {}
    for evs in per_thread.values():
        for t0, t1, path in leaf_events(evs):
            layer = layer_of(path)
            out[layer] = out.get(layer, 0.0) + (t1 - t0) / 1e6
    return out


# two ops that only touch (the next starts within 2 ns of this one's end,
# as stamps rounded to the nanosecond can) do not nest
_TOUCH_US = 2e-3


def leaf_events(evs: List[Tuple[float, float, str]]
                ) -> List[Tuple[float, float, str]]:
    """One thread's ``(t0, t1, scope_path)`` events -> the leaves only,
    each with its own path or, lacking one, its nearest container's."""
    evs = sorted(evs, key=lambda ev: (ev[0], -ev[1]))
    out = []
    stack: List[Tuple[float, str]] = []       # (t1, inherited path)
    for i, (t0, t1, path) in enumerate(evs):
        while stack and stack[-1][0] <= t0:
            stack.pop()
        if not path and stack:
            path = stack[-1][1]
        if i + 1 < len(evs) and evs[i + 1][0] < t1 - _TOUCH_US:
            stack.append((t1, path))          # a container
        else:
            out.append((t0, t1, path))
    return out


# ---------------------------------------------------------- attribution


def attribute_classed(classed: Dict[str, List[Interval]],
                      window: Optional[Interval] = None) -> Dict[str, Any]:
    """One device track's compute/comm interval unions → the exact,
    mutually exclusive decomposition (all times in SECONDS, inputs µs):

        compute_s + exposed_comm_s + idle_s == window_s

    ``comm_s`` is the TOTAL collective time (for "how much comm is
    there"); ``exposed_comm_s = comm \\ compute`` is the part the
    schedule failed to hide — the number overlap work must drive down.
    """
    compute = merge_intervals(classed.get("compute", []))
    comm = merge_intervals(classed.get("comm", []))
    if window is None:
        allv = compute + comm
        window = ((min(lo for lo, _ in allv), max(hi for _, hi in allv))
                  if allv else (0.0, 0.0))
    win_us = max(0.0, window[1] - window[0])
    exposed = subtract_intervals(comm, compute)
    busy = merge_intervals(compute + comm)
    compute_us = measure(compute)
    comm_us = measure(comm)
    exposed_us = measure(exposed)
    idle_us = max(0.0, win_us - measure(busy))
    out = {
        "window_s": win_us / 1e6,
        "compute_s": compute_us / 1e6,
        "comm_s": comm_us / 1e6,
        "exposed_comm_s": exposed_us / 1e6,
        "idle_s": idle_us / 1e6,
    }
    if win_us > 0:
        out["compute_frac"] = compute_us / win_us
        out["exposed_comm_frac"] = exposed_us / win_us
        out["idle_frac"] = idle_us / win_us
    else:
        out["compute_frac"] = out["exposed_comm_frac"] = None
        out["idle_frac"] = None
    return out


def attribute_tracks(tracks: Dict[str, List[Tuple[float, float, str]]]
                     ) -> Dict[str, Any]:
    """All device tracks of one capture → per-device attribution plus
    the per-class interval unions (the merged-trace export reuses
    them). The idle window is the CAPTURE-wide op extent, shared by
    every track, so a device idling while its peers compute reads as
    idle — the straggler signature."""
    classed: Dict[str, Dict[str, List[Interval]]] = {}
    lo = hi = None
    for name, ops in tracks.items():
        c = classed.setdefault(name, {"compute": [], "comm": []})
        for t0, t1, op in ops:
            cls = classify(op)
            if cls is None:
                continue
            c[cls].append((t0, t1))
            lo = t0 if lo is None else min(lo, t0)
            hi = t1 if hi is None else max(hi, t1)
    window = (lo, hi) if lo is not None else None
    devices = {name: attribute_classed(c, window)
               for name, c in sorted(classed.items())}
    intervals = {name: {cls: merge_intervals(iv)
                        for cls, iv in c.items()}
                 for name, c in classed.items()}
    pod = {
        "devices": len(devices),
        "window_s": (max(0.0, (window[1] - window[0]) / 1e6)
                     if window else 0.0),
        "compute_s": sum(d["compute_s"] for d in devices.values()),
        "comm_s": sum(d["comm_s"] for d in devices.values()),
        "exposed_comm_s": sum(d["exposed_comm_s"]
                              for d in devices.values()),
    }
    denom = pod["window_s"] * max(len(devices), 1)
    pod["exposed_comm_frac"] = (pod["exposed_comm_s"] / denom
                                if denom > 0 else None)
    return {"devices": devices, "intervals": intervals, "pod": pod,
            "window_us": window}


def analyze_capture(capture_dir: str) -> Dict[str, Any]:
    """Parse every capture file under ``capture_dir`` and attribute
    device time. Raises ``FileNotFoundError`` when the dir holds no
    trace-event JSON (an aborted capture)."""
    paths = find_captures(capture_dir)
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json(.gz) under {capture_dir}")
    tracks: Dict[str, List[Tuple[float, float, str]]] = {}
    by_scope: Dict[str, float] = {}
    for p in paths:
        doc = load_capture_doc(p)
        for name, ops in device_op_tracks(doc).items():
            tracks.setdefault(name, []).extend(ops)
        for layer, sec in scope_seconds(doc).items():
            by_scope[layer] = by_scope.get(layer, 0.0) + sec
    out = attribute_tracks(tracks)
    out["capture_files"] = paths
    # device seconds per layer (scopes.layer_of), largest first; ""
    # is the time no scope names. Empty where the capture carries no
    # name stacks (the CPU backend's)
    out["by_scope"] = {k: round(v, 6) for k, v in sorted(
        by_scope.items(), key=lambda kv: -kv[1])} \
        if any(by_scope) else {}
    return out


# ------------------------------------------------------------ verdict

# Exposed-communication gate: above this fraction of the device window
# spent on UN-hidden collectives, the run is flagged — the pod is
# paying for its fabric in steps/s. Advisory, like the staging and
# straggler gates; env override TPUDIST_COMM_EXPOSED_MAX (call time).
# The threshold itself lives in tpudist.rules, shared with the live
# alert engine so mid-run and at-exit grading cannot drift. DCN-labeled
# rows (a data axis crossing slices) grade against their own ceiling.
COMM_EXPOSED_MAX = rules_lib.COMM_EXPOSED_MAX
COMM_EXPOSED_MAX_DCN = rules_lib.COMM_EXPOSED_MAX_DCN


def comm_status(exposed_frac: Optional[float],
                max_frac: Optional[float] = None,
                fabric: Optional[str] = None) -> str:
    """Three-valued exposed-communication verdict: UNGATEABLE when no
    device window was measured (capture off or empty), else
    SUCCESS/FAIL by whether the exposed-comm fraction of the device
    window stays under the threshold. ``fabric`` selects the per-fabric
    default (``tpudist.rules.resolve_comm``): a data axis crossing
    slices grades against the DCN ceiling
    (``TPUDIST_COMM_EXPOSED_MAX_DCN``) — a slower fabric honestly costs
    more exposure before the run is flagged — while ICI rows keep
    ``TPUDIST_COMM_EXPOSED_MAX``. An explicit ``max_frac`` wins."""
    if max_frac is None:
        max_frac = rules_lib.resolve_comm(fabric)
    if exposed_frac is None:
        return UNGATEABLE
    return SUCCESS if exposed_frac <= max_frac else FAIL


# --------------------------------------------- merged-trace device rows

# Device tracks ride under each host's pid in pod_trace.json on
# synthetic tids far above the tracer's per-thread ids.
DEVICE_TID_BASE = 1000
DEVTIME_CAT = "devtime"


def device_events(analysis: Dict[str, Any], *, process_index: int,
                  anchor_us: float) -> List[Dict[str, Any]]:
    """The capture's per-class busy intervals as Chrome trace events for
    the pod merge: one synthetic thread per device track under the
    host's pid, events named ``compute``/``comm`` over the merged
    interval unions (coalesced — per-op events would bloat
    ``pod_trace.json`` by orders of magnitude and add nothing the
    report's interval math needs).

    ``anchor_us`` is the host's ``perf_counter_ns()/1e3`` sampled
    immediately before ``start_trace``: the profiler stamps event
    timestamps relative to session start on the same monotonic clock,
    so ``anchor_us + ts`` lands the device ops on the host tracer's
    timebase and the existing clock-offset merge aligns them pod-wide.
    """
    out: List[Dict[str, Any]] = []
    for i, (name, classed) in enumerate(sorted(
            analysis["intervals"].items())):
        tid = DEVICE_TID_BASE + i
        out.append({"ph": "M", "name": "thread_name",
                    "pid": process_index, "tid": tid,
                    "args": {"name": f"device:{name}"}})
        for cls in ("compute", "comm"):
            for lo, hi in classed.get(cls, []):
                out.append({"name": cls, "cat": DEVTIME_CAT, "ph": "X",
                            "ts": anchor_us + lo, "dur": hi - lo,
                            "pid": process_index, "tid": tid,
                            "args": {"device": name}})
    return out


# ------------------------------------------------------ window capture


class WindowProfiler:
    """``--profile-window N``: capture N mid-run supersteps with
    ``jax.profiler`` into ``<out_dir>/worker<i>`` and hand the capture
    to :func:`analyze_capture` at run end.

    Unlike full-run ``--profile-dir`` (a manual debug tool that forces
    per-step dispatch and disables autotuning), the window is cheap and
    composes with everything: it arms at the MIDDLE epoch's first
    dispatch (steady state — compile and staging fill are over), counts
    dispatches, fences once, and stops. The only perturbation is the
    capture overhead inside the window plus that one fence; device math
    is untouched, so step losses stay bitwise-identical to an
    uncaptured run (pinned in tests).

    Thread-safety: the stall watchdog calls :meth:`emergency_stop` from
    its own thread when a run hangs with the window open — the partial
    capture is kept next to the flight record, so even a hung run
    yields a device timeline. ``_stop`` is guarded by a lock and never
    fences (the fence happens in :meth:`note_dispatch` BEFORE the lock,
    so a wedged device cannot deadlock the watchdog against the main
    thread).
    """

    def __init__(self, out_dir: str, n_dispatches: int, *,
                 process_index: int = 0, trigger_epoch: int = 0):
        if n_dispatches < 1:
            raise ValueError(
                f"profile window must be >= 1 dispatch, got {n_dispatches}")
        self.capture_dir = os.path.join(out_dir,
                                        f"worker{process_index}")
        self.n = n_dispatches
        self.trigger_epoch = trigger_epoch
        self.process_index = process_index
        self.state = "armed"            # armed -> open -> done
        self.seen = 0
        self.captured = False
        self.anchor_ns: Optional[int] = None
        self._lock = threading.Lock()
        self._span = None

    @classmethod
    def from_config(cls, cfg, *, out_dir: str,
                    process_index: int = 0) -> Optional["WindowProfiler"]:
        """``None`` when the window is off (the train loop's calls all
        no-op through a plain ``if win is not None``)."""
        from tpudist.config import resolve_profile_window
        n = resolve_profile_window(cfg)
        if n <= 0:
            return None
        # mid-run: the middle epoch's first dispatches are steady state
        # (past compile, past the first epoch's staging fill)
        return cls(os.path.join(out_dir, "profile"), n,
                   process_index=process_index,
                   trigger_epoch=max(0, cfg.epochs // 2))

    # ------------------------------------------------------ train hooks
    def maybe_start(self, epoch: int) -> None:
        """Epoch-top hook: open the capture at the trigger epoch."""
        if self.state != "armed" or epoch < self.trigger_epoch:
            return
        import jax

        from tpudist.obs import trace as trace_lib
        os.makedirs(self.capture_dir, exist_ok=True)
        self._span = trace_lib.get().begin("profile_window",
                                           cat="profile", n=self.n)
        # the anchor must be read BEFORE start_trace: the profiler
        # stamps its session epoch (the ts origin) during the call
        self.anchor_ns = time.perf_counter_ns()
        # device and runtime events only: with the Python tracer on every
        # call is an event, and the tracer's mirrored spans drown in them
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(self.capture_dir, profiler_options=opts)
        self.state = "open"

    def note_dispatch(self, result: Any = None) -> None:
        """Per-dispatch hook; closes the window after ``n`` dispatches.
        The fence (one host transfer) makes the captured supersteps'
        device execution actually land inside the capture — stopping
        behind async dispatch would truncate the timeline."""
        if self.state != "open":
            return
        self.seen += 1
        if self.seen < self.n:
            return
        if result is not None:
            import jax
            try:
                jax.device_get(result)
            except Exception:
                pass
        self._stop()

    def close(self) -> None:
        """Run-end backstop: a window larger than the run still stops
        cleanly (partial capture). Idempotent."""
        self._stop()

    def emergency_stop(self) -> Optional[str]:
        """Watchdog hook: stop an open capture WITHOUT fencing (the
        device may be the thing that hung) and report the capture path
        for the flight record; ``None`` when no window was open."""
        if self.state != "open":
            return None
        self._stop()
        return self.capture_dir if self.captured else None

    def _stop(self) -> None:
        with self._lock:
            if self.state != "open":
                return
            self.state = "done"
            import jax
            try:
                jax.profiler.stop_trace()
                self.captured = True
            except Exception:
                pass
            if self._span is not None:
                from tpudist.obs import trace as trace_lib
                trace_lib.get().end(self._span)
                self._span = None
