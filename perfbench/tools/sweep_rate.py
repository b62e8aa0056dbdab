"""Find the knee once: one process, one engine, the cell's own length mix
offered at each of a list of rates for ``--seconds`` seconds. For each rate
it prints the time to first token of the first and last third of the
requests (a backlog that grows shows as a last third far above the first),
how long the queue took to drain after the last arrival, and the completed
tokens a second. The knee is written into the cell's traffic file by hand.

    python3 perfbench/tools/sweep_rate.py --workload W --rates 2,4,6,8 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run as run_mod                    # noqa: E402
from perfbench.lib import manifest as manifest_lib      # noqa: E402
from perfbench.lib import stats                         # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    manifest = manifest_lib.load()
    ns = argparse.Namespace(workload=a.workload, seed=a.seed,
                            seconds=a.seconds, trace=0)
    ctx = run_mod.Ctx(ns, manifest)
    ctx.workdir = tempfile.mkdtemp(prefix="perfbench-")
    dev = run_mod.setup_jax(ctx)
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched
    from perfbench.lib import serve_entry as se
    from perfbench.lib import traffic as traffic_lib
    engine, params = se.build_engine(ctx)
    e = ctx.traffic["engine"]
    for rate in (float(r) for r in a.rates.split(",")):
        trace_lib.configure(enabled=True)
        mix = dict(ctx.traffic, rate_rps=rate)
        reqs = traffic_lib.serve_requests(mix, a.seed, a.seconds,
                                          ctx.config["vocab_size"],
                                          e["prompt_pad"])
        requests = [sched.Request(rid=i, arrival_s=t, tokens=tok,
                                  prompt_len=pl, max_new=mn)
                    for i, (t, tok, pl, mn) in enumerate(reqs)]
        rec = se.Recorder()
        t0 = time.perf_counter()
        summary = sched.run_serve(engine, params, requests, metrics=rec,
                                  clock=rec.clock)
        wall = time.perf_counter() - t0
        r = se.reduce_events(rec, reqs)
        n = len(reqs)
        third = max(1, n // 3)
        ttft = r["ttft_s"]
        out = {"rate_rps": rate, "requests": n,
               "completed": summary["completed"], "wall_s": wall,
               "drain_s": wall - a.seconds,
               "ttft_first_third_p50_ms": 1e3 * stats.median(ttft[:third]),
               "ttft_last_third_p50_ms": 1e3 * stats.median(ttft[-third:]),
               "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
               "tpot_p95_ms": 1e3 * stats.percentile(r["tpot_s"], 95),
               "tokens_per_s": sum(r["gen"].values()) / wall,
               "decode_dispatches": summary["dispatches"],
               "device": dev}
        print("SWEEP " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
