"""Read, over many seeds in ONE process, the numbers that decide
``correct``: the program's (the lower reading), the control's (the
reference in fp8 put in the program's place) and the planted faults'. Runs
on the chip at the cell's own size; its output is what PERF.md's limits are
set from. Every reading is judged by the limits in the cell's own file, as
a run of the benchmark judges it: the program has to come out correct, the
control and each fault not.

    python3 perfbench/tools/limits.py --workload W --seeds 1,2,3 \
        [--control 3] [--seconds 12] [--out chiprun_out/limits.jsonl]
    python3 perfbench/tools/limits.py --workload W --rejudge rows.jsonl

The second form reads rows that an earlier call recorded and judges them by
the limits as they stand now (no chip: it is arithmetic on the readings).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run as run_mod                    # noqa: E402
from perfbench.lib import manifest as manifest_lib      # noqa: E402


def values(compared: dict) -> dict:
    return {k: v["value"] for k, v in compared.items()}


def verdicts(row: dict, limits: dict) -> dict:
    """{reading: [correct, the numbers over their limit]} for the program,
    the control and each fault of one seed's row."""
    out = {}
    for name, got in row.items():
        if name != "program" and not name.startswith(("control_", "fault_")):
            continue
        over = [k for k, lim in limits.items() if k in got and got[k] > lim]
        out[name] = [not over, over]
    return out


ALTS = {"control_fp8": {"mode": "fp8"},
        "fault_half_batch": {"rows": "half"},
        "fault_state_unchanged": {"frozen": True}}


def train_seed(ctx, with_control: bool, alts) -> dict:
    from perfbench.lib import reference as ref_lib
    from perfbench.lib import train_entry as te
    limits = ctx.traffic["limits"]
    first, lr, rows = te.first_steps(ctx)
    ref = ref_lib.train_steps(ctx.seed, ctx.config, rows, lr)
    out = {"program": values(te.compare(first, ref, limits, rows))}
    if with_control:
        for name in alts:
            kw = dict(ALTS[name])
            if kw.get("rows") == "half":
                kw["rows"] = range(rows.shape[1] // 2)
            alt = ref_lib.train_steps(ctx.seed, ctx.config, rows, lr, **kw)
            out[name] = values(te.compare(alt, ref, limits))
    return out


def serve_seed(ctx, with_control: bool, alts=None) -> dict:
    from perfbench.lib import serve_entry as se
    res = se.window(ctx)
    got = se.score(ctx, res["sample"], "fp8" if with_control else None)
    out = {"program": {"logit_gap_max": float(got["gaps"].max()),
                       "tokens": int(len(got["gaps"])),
                       "nonzero": int((got["gaps"] > 0).sum())},
           "e2e": res["e2e"], "failed": res["failed"]}
    if with_control:
        out["control_fp8"] = {
            "logit_gap_max": float(got["control_gaps"].max()),
            "nonzero": int((got["control_gaps"] > 0).sum())}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rejudge", help="rows recorded by an earlier call")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read control and faults")
    ap.add_argument("--alts", default=",".join(ALTS),
                    help="train: which of control and faults to read")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", default="chiprun_out/limits.jsonl")
    a = ap.parse_args()
    manifest = manifest_lib.load()
    if a.rejudge:
        ns = argparse.Namespace(workload=a.workload, seed=0, seconds=1.0,
                                trace=0)
        limits = run_mod.Ctx(ns, manifest).traffic["limits"]
        with open(a.rejudge, encoding="utf-8") as f:
            for row in map(json.loads, f):
                if row["workload"] == a.workload:
                    print("JUDGED " + json.dumps(
                        {"seed": row["seed"], **verdicts(row, limits)}))
        return 0
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    dev = None
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        ns = argparse.Namespace(workload=a.workload, seed=seed,
                                seconds=a.seconds, trace=0)
        ctx = run_mod.Ctx(ns, manifest)
        ctx.arm_compile_count = lambda on: None
        if dev is None:
            dev = run_mod.setup_jax(ctx)
        with tempfile.TemporaryDirectory(prefix="perfbench-") as d:
            ctx.workdir = d
            fn = train_seed if ctx.traffic["entry"] == "train" else serve_seed
            row = fn(ctx, n < a.control, a.alts.split(","))
        row.update(workload=a.workload, seed=seed, device=dev,
                   correct=verdicts(row, ctx.traffic["limits"]))
        print("LIMITS " + json.dumps(row), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
