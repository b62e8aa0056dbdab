"""Collective bandwidth sweep (BASELINE.json config #4).

The measured analogue of "did NCCL work" — the reference only ever observed
its collectives as pass/fail through the training job; this sweeps message
sizes 1MB→1GB per collective kind and reports bus bandwidth and % of the
hardware's theoretical ring peak.

Run:  python -m tpudist.bench.sweep [--kinds all_reduce,...] [--axis data]
                                    [--min-mb 1] [--max-mb 1024]
                                    [--min-pct-peak 90] [--verdict-path p]
                                    [--out sweep.jsonl]

The sweep is a GATE, not just a measurement (the reference turns every
signal into a hard pass/fail, ci:152-181): each collective kind's BEST
bucket must reach ``--min-pct-peak`` percent of the ICI ring peak
(latency-bound small messages are informational), else exit 1 and write
``fail`` to ``--verdict-path`` for the launcher/CI poller. ``--out`` writes
the records as clean JSONL to a file, so launcher stdout noise (ssh/gcloud
banners) never pollutes the artifact.
"""

from __future__ import annotations

import argparse
import json
from typing import List, Optional

import jax

from tpudist.config import ParallelConfig
from tpudist.metrics import device_kind, log0
from tpudist.ops import collectives
from tpudist.parallel import build_mesh

# Approximate per-chip ICI ring peaks, GB/s of bus bandwidth along a 1-D
# bidirectional ring (2 links active). Public figures: v4 ≈ 2×45, v5e ≈
# 2×50, v5p ≈ 2×100 GB/s per link-direction. Used only to report % of
# peak; absolute GB/s is always printed.
RING_PEAK_GBPS = {
    "TPU v4": 90.0,
    "TPU v5 lite": 100.0,
    "TPU v5e": 100.0,
    "TPU v5": 200.0,
    "TPU v5p": 200.0,
    "TPU v6 lite": 180.0,
}


def ring_peak_gbps(kind_name: Optional[str] = None) -> Optional[float]:
    name = kind_name or device_kind()
    for k, v in sorted(RING_PEAK_GBPS.items(), key=lambda kv: -len(kv[0])):
        if name.startswith(k):
            return v
    return None


def sweep_sizes(min_mb: float = 1, max_mb: float = 1024) -> List[int]:
    """1MB → 1GB in ×4 steps (6 buckets at defaults)."""
    sizes, s = [], int(min_mb * 2**20)
    top = int(max_mb * 2**20)
    while s <= top:
        sizes.append(s)
        s *= 4
    return sizes


def axis_fabric(mesh, axis: str) -> str:
    """Label a mesh axis ``ici`` or ``dcn``. The implementation moved to
    :func:`tpudist.parallel.mesh.axis_fabric` (an axis's fabric is a
    mesh property, now also consumed by the devtime per-fabric comm
    grading and the overlap bench — and it honors the scripted
    ``TPUDIST_SLICE_MAP`` 2-slice DCN stand-in); this alias keeps the
    sweep's documented surface."""
    from tpudist.parallel import mesh as mesh_lib
    return mesh_lib.axis_fabric(mesh, axis)


def collectives_artifact(records: List[dict]) -> dict:
    """BENCH_COLLECTIVES.json on the same harness shape as the other
    BENCH_* artifacts: one headline metric — the best all-reduce bus
    bandwidth, the fabric-acceptance number BASELINE.json names — and
    the full per-kind per-size rows in ``detail``. When the sweep did
    not include all_reduce, the headline names the kind it actually
    measured instead of mislabeling another kind's bandwidth. Axis and
    fabric come from the records themselves (every row carries them),
    so there is exactly one derivation."""
    kind = "all_reduce"
    if not any(r["kind"] == kind for r in records):
        kind = max(records, key=lambda r: r["bus_gbps"])["kind"] \
            if records else "all_reduce"
    best = max((r["bus_gbps"] for r in records if r["kind"] == kind),
               default=0.0)
    return {
        "metric": f"collective_{kind}_best_bus_gbps",
        "value": round(best, 4),
        "unit": f"GB/s bus bandwidth (best {kind} bucket)",
        "detail": {
            "device": device_kind(),
            "n_devices": records[0]["n_devices"] if records else 0,
            "axis": records[0]["axis"] if records else None,
            "fabric": records[0]["fabric"] if records else None,
            "kinds": sorted({r["kind"] for r in records}),
            "rows": records,
        },
    }


def write_collectives_artifact(records: List[dict], path: str) -> dict:
    """The ONE writer of BENCH_COLLECTIVES.json (this module's
    ``--bench-out``; the launcher's sweep lane calls it)."""
    art = collectives_artifact(records)
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return art


def run_sweep(kinds=("all_reduce",), axis: str = "data", *,
              min_mb: float = 1, max_mb: float = 1024, iters: int = 10,
              peak_gbps: Optional[float] = None) -> List[dict]:
    """Returns one record per (kind, size): message size, time, algo/bus
    GB/s, % of ring peak (None off-TPU or unknown chip). ``peak_gbps``
    overrides the built-in chip table — the operator escape hatch for a
    chip generation RING_PEAK_GBPS doesn't know yet."""
    mesh = build_mesh(ParallelConfig())
    n = mesh.shape[axis]
    peak = peak_gbps or ring_peak_gbps()
    fabric = axis_fabric(mesh, axis)
    out = []
    for kind in kinds:
        for size in sweep_sizes(min_mb, max_mb):
            t = collectives.time_collective(kind, mesh, axis,
                                            message_bytes=size, iters=iters)
            rec = {
                "kind": kind, "n_devices": n,
                "axis": axis, "fabric": fabric,
                "message_bytes": t.message_bytes,
                "mean_s": t.mean_s, "min_s": t.min_s,
                "algo_gbps": t.algo_gbps, "bus_gbps": t.bus_gbps,
                "pct_of_ring_peak": (100 * t.bus_gbps / peak
                                     if peak and n > 1 else None),
            }
            out.append(rec)
            log0(json.dumps(rec))
    return out


def gate(records: List[dict], min_pct_peak: float) -> dict:
    """Apply the bandwidth acceptance gate: per collective kind, the best
    bucket's ``pct_of_ring_peak`` must reach ``min_pct_peak``.

    Returns {"ok": bool|None, "per_kind": {kind: best_pct}, "reason": str}.
    ``ok`` is None (gate not applicable, NOT a pass) when nothing could be
    measured against a peak — single-device mesh or unknown chip."""
    per_kind: dict = {}
    for r in records:
        if r["pct_of_ring_peak"] is None:
            continue
        best = per_kind.get(r["kind"])
        if best is None or r["pct_of_ring_peak"] > best:
            per_kind[r["kind"]] = r["pct_of_ring_peak"]
    if not per_kind:
        return {"ok": None, "per_kind": {},
                "reason": "no gateable records (single device or unknown "
                          "chip peak)"}
    bad = {k: v for k, v in per_kind.items() if v < min_pct_peak}
    if bad:
        return {"ok": False, "per_kind": per_kind,
                "reason": f"below {min_pct_peak}% of ring peak: " + ", ".join(
                    f"{k}={v:.1f}%" for k, v in sorted(bad.items()))}
    return {"ok": True, "per_kind": per_kind,
            "reason": f"all kinds ≥ {min_pct_peak}% of ring peak"}


def main(argv=None) -> int:
    from tpudist.utils import tune_tpu
    tune_tpu()
    # multi-host slices need distributed init (all workers run the sweep;
    # the collectives span the full pod); single-host this is a no-op
    from tpudist.parallel import distributed
    distributed.initialize()
    p = argparse.ArgumentParser()
    p.add_argument("--kinds", type=str, default="all_reduce")
    p.add_argument("--axis", type=str, default="data")
    p.add_argument("--min-mb", type=float, default=1)
    p.add_argument("--max-mb", type=float, default=1024)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--min-pct-peak", type=float, default=90.0,
                   help="acceptance threshold: best bucket per kind must "
                        "reach this %% of the ICI ring peak (BASELINE.md); "
                        "<=0 disables the gate")
    p.add_argument("--peak-gbps", type=float, default=None,
                   help="operator override for the ICI ring peak (GB/s) — "
                        "gates against this instead of the built-in chip "
                        "table; required to gate on a chip kind the table "
                        "doesn't know")
    p.add_argument("--verdict-path", type=str, default=None,
                   help="write success/fail here (local path or gs://) — "
                        "the reference's job_status.txt protocol")
    p.add_argument("--out", type=str, default=None,
                   help="also write records as clean JSONL to this file")
    p.add_argument("--bench-out", type=str, default=None,
                   help="also write the BENCH_COLLECTIVES.json artifact "
                        "here (the BASELINE.json harness shape: headline "
                        "metric + per-kind per-size rows with ICI/DCN "
                        "fabric labels; the launcher's sweep lane "
                        "writes it through this flag)")
    # strict: a mistyped flag must error, not silently run a full 1GB sweep
    args = p.parse_args(argv)
    records = run_sweep(tuple(args.kinds.split(",")), args.axis,
                        min_mb=args.min_mb, max_mb=args.max_mb,
                        iters=args.iters, peak_gbps=args.peak_gbps)
    if args.out and jax.process_index() == 0:
        with open(args.out, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    if args.bench_out and jax.process_index() == 0:
        write_collectives_artifact(records, args.bench_out)

    if args.min_pct_peak <= 0:
        return 0
    g = gate(records, args.min_pct_peak)
    log0(json.dumps({"sweep_gate": g}))
    from tpudist import verdict
    if g["ok"] is None:
        # Nothing could be compared against a peak (unknown chip kind with
        # no --peak-gbps override, or a single-device mesh). Absolute GB/s
        # was still measured and recorded; publish the distinct UNGATEABLE
        # status (exit 3) so the first run on a new TPU generation doesn't
        # read as a bandwidth regression — a real below-threshold result
        # stays a hard fail. Still nonzero: absent evidence must not
        # publish success (the reference's missing-status-file stance).
        if args.verdict_path:
            verdict.write_final_status(args.verdict_path, verdict.UNGATEABLE)
        return 3
    if args.verdict_path:
        verdict.write_final_verdict(args.verdict_path, g["ok"] is True)
    return 0 if g["ok"] is True else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
