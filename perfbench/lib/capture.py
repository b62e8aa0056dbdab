"""From a ``jax.profiler`` capture to device busy time, kernel time and
the breakdown. The benchmark's own copy of the interval arithmetic and of
the trace-event parser in ``tpudist/obs/devtime.py`` (which parsed a real
v5e capture in PR 21), so that no later PR to the program can move a
number reported here. Times in the capture are microseconds."""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

_OP_NAME_RE = re.compile(r"^[A-Za-z][A-Za-z0-9_.\-]*$")
# control flow on the device's op line spans the ops of its body (a scan's
# ``while`` covers the whole dispatch, gaps included): a container, not
# work, so it counts neither as busy time nor in the breakdown
_CONTAINER_RE = re.compile(r"^(while|cond|conditional|call)([.\-_]\d+)*$")


def is_op(name: str) -> bool:
    """A device op that did work: not runtime noise, not a container."""
    return bool(name and _OP_NAME_RE.match(name)
                and not _CONTAINER_RE.match(name))


def merge_intervals(intervals):
    """Sorted disjoint union (zero-length dropped)."""
    out = []
    for lo, hi in sorted((lo, hi) for lo, hi in intervals if hi > lo):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def measure(disjoint) -> float:
    return sum(hi - lo for lo, hi in disjoint)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def find_captures(capture_dir: str):
    out = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        out.extend(glob.glob(os.path.join(capture_dir, "**", pat),
                             recursive=True))
    return sorted(out)


def load_doc(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return json.load(f)


def device_tracks(doc: dict) -> dict:
    """{track: [(t0_us, t1_us, op_name)]}. On the TPU one process per
    device, op executions on its 'XLA Ops' thread. The CPU backend has no
    device process: its op events sit on the client's pool threads and fold
    into one track (so the plumbing can be rehearsed without a chip)."""
    procs, threads = {}, {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    dev = {pid: n.split("/device:", 1)[1] for pid, n in procs.items()
           if n.startswith("/device:")}
    with_ops = {pid for (pid, _), tn in threads.items()
                if pid in dev and "XLA Ops" in tn}
    tracks: dict = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        pid, name = e.get("pid"), e.get("name", "")
        tn = threads.get((pid, e.get("tid")), "")
        if pid in dev:
            if pid in with_ops and "XLA Ops" not in tn:
                continue
            track = dev[pid]
        elif tn.startswith(("tf_XLATfrtCpuClient", "tf_XLAEigen",
                            "tf_XLAPjRtCpuClient")):
            track = "host-cpu"
        else:
            continue
        if not is_op(name):
            continue
        t0 = float(e["ts"])
        tracks.setdefault(track, []).append((t0, t0 + float(e["dur"]), name))
    return tracks


def load_tracks(capture_dir: str) -> dict:
    paths = find_captures(capture_dir)
    if not paths:
        raise FileNotFoundError(f"no *.trace.json(.gz) under {capture_dir}")
    tracks: dict = {}
    for p in paths:
        for name, ops in device_tracks(load_doc(p)).items():
            tracks.setdefault(name, []).extend(ops)
    return tracks


def first_op_us(tracks: dict) -> float:
    return min(t0 for ops in tracks.values() for t0, _, _ in ops)


def cut(tracks: dict, lo: float, hi: float) -> dict:
    """The tracks with every op clipped to [lo, hi): what a session that
    stayed open longer than the stretch it was opened for is cut to."""
    out = {}
    for name, ops in tracks.items():
        kept = [(max(a, lo), min(b, hi), n) for a, b, n in ops
                if min(b, hi) > max(a, lo)]
        if kept:
            out[name] = kept
    return out


def reduce_tracks(tracks: dict) -> dict:
    """Busy and idle over the capture. The window is the capture-wide
    extent of device ops, shared by every device; busy is the union of each
    device's ops clipped to it, averaged over devices, so busy <= window."""
    if not tracks or not any(tracks.values()):
        raise ValueError("the capture holds no device operation")
    lo = first_op_us(tracks)
    hi = max(t1 for ops in tracks.values() for _, t1, _ in ops)
    per_dev = [measure(clip(merge_intervals([(a, b) for a, b, _ in ops]),
                            lo, hi)) for ops in tracks.values()]
    n = len(per_dev)
    window_s = (hi - lo) / 1e6
    busy_s = min(sum(per_dev) / n / 1e6, window_s)
    return {"window_s": window_s, "busy_s": busy_s, "devices": n,
            "lo_us": lo, "hi_us": hi}


def op_seconds(tracks: dict, pattern: str) -> float:
    """Summed device time of the ops whose name matches, mean over devices."""
    rx = re.compile(pattern)
    tot = sum(b - a for ops in tracks.values() for a, b, n in ops
              if rx.search(n))
    return tot / max(len(tracks), 1) / 1e6


def _base(name: str) -> str:
    return re.sub(r"[.\-_]?\d+$", "", name)


def breakdown(tracks: dict, host_spans=None, top: int = 10) -> dict:
    """The ten device ops that took most time (by name without its
    numbering, mean over devices) and the ten longest idle gaps of the
    first device, each named by the host span that covers most of it."""
    n = max(len(tracks), 1)
    by = {}
    for ops in tracks.values():
        for a, b, name in ops:
            by[_base(name)] = by.get(_base(name), 0.0) + (b - a)
    dev_ops = sorted(([k, v / n / 1e6] for k, v in by.items()),
                     key=lambda r: -r[1])[:top]
    first = next(iter(tracks.values()))
    busy = merge_intervals([(a, b) for a, b, _ in first])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    rows = []
    for lo, hi in gaps[:top]:
        label, best = "host:unattributed", 0.0
        for s in host_spans or ():
            ov = min(hi, s["t1_us"]) - max(lo, s["t0_us"])
            if ov > best:
                label, best = "host:" + s["name"], ov
        rows.append([label, (hi - lo) / 1e6])
    return {"device_ops": dev_ops, "idle_gaps": rows}
