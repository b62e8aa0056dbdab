"""The tools of a serve cell, by the cell's name: the sweep for the knee,
the compile-only sizing and the judging of control and planted faults. The
cell's own entry (``perfbench/lib/<entry>_entry.py``, named by its traffic
file) is what they drive: it has to offer ``new_engine(ctx, mesh)``,
``build_engine``, ``requests_of``, ``reduce_events``, ``Recorder``,
``window``, ``score``, ``compared``, ``faults()`` and ``model_lib``, as
``serve_longcat_entry`` does (``cell_cohere2moe.py`` and ``cell_sdar.py``
beside this file are the older cells' own copies).

    python3 perfbench/tools/cell_serve.py sweep --workload W --rates 4,6,8 --seconds 30
    JAX_PLATFORMS=cpu python3 perfbench/tools/cell_serve.py compile --workload W
    python3 perfbench/tools/cell_serve.py limits --workload W --seeds 1,2,3 [--control 3]
    python3 perfbench/tools/cell_serve.py limits --workload W --seeds 4,5 --only bf16 --layers 2

``sweep`` and ``limits`` run on the chip at the cell's own size, in ONE
process each; ``compile`` runs here, for no chip time, and what it prints
is "compiled, not run".
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import manifest as manifest_lib      # noqa: E402


def ctx_for(args, seed, seconds):
    from perfbench import run as run_mod
    ns = argparse.Namespace(workload=args.workload, seed=seed,
                            seconds=seconds, trace=0)
    ctx = run_mod.Ctx(ns, manifest_lib.load())
    ctx.arm_compile_count = lambda on: None
    ctx.workdir = tempfile.mkdtemp(prefix="perfbench-")
    return ctx


def entry_of(ctx):
    return importlib.import_module(
        "perfbench.lib." + ctx.traffic["entry"] + "_entry")


def sweep(a) -> int:
    """One engine, the cell's own length mix offered at each rate for
    ``--seconds``: the time to first token of the first and last third of
    the requests (a backlog that grows shows as a last third far above
    the first), how long the queue took to drain, completed tokens a
    second. The knee is written into the cell's traffic file by hand."""
    from perfbench import run as run_mod
    from perfbench.lib import stats
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched
    ctx = ctx_for(a, a.seed, a.seconds)
    entry = entry_of(ctx)
    dev = run_mod.setup_jax(ctx)
    engine, params = entry.build_engine(ctx)
    for rate in (float(r) for r in a.rates.split(",")):
        trace_lib.configure(enabled=True)
        reqs, requests = entry.requests_of(
            ctx, dict(ctx.traffic, rate_rps=rate))
        rec = entry.Recorder()
        t0 = time.perf_counter()
        summary = sched.run_serve(engine, params, requests, metrics=rec,
                                  clock=rec.clock)
        wall = time.perf_counter() - t0
        r = entry.reduce_events(rec, reqs)
        third = max(1, len(reqs) // 3)
        ttft = r["ttft_s"]
        print("SWEEP " + json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "completed": summary["completed"], "wall_s": wall,
            "drain_s": wall - a.seconds,
            "ttft_first_third_p50_ms": 1e3 * stats.median(ttft[:third]),
            "ttft_last_third_p50_ms": 1e3 * stats.median(ttft[-third:]),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * stats.median(r["tpot_s"]),
            "tokens_per_s": sum(r["gen"].values()) / wall,
            "decode_dispatches": summary["dispatches"],
            "active_slots_peak": summary["active_slots_peak"],
            "kv_pages_used_peak": summary["kv_pages_used_peak"],
            "device": dev}), flush=True)
    return 0


def compile_only(a) -> int:
    """The cell's prefill and decode programs lowered for a described
    ``v5e:2x2`` at the real sizes with the chip's own compiler."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from perfbench.tools import compile_rehearsal as cr
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cr.steer()
    ctx = ctx_for(a, 1, 10)
    entry = entry_of(ctx)
    e = ctx.traffic["engine"]
    mc = entry.model_config(ctx)
    mesh = cr.mesh_for(topo, ctx.chips)
    rep = NamedSharding(mesh, P())
    described = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=rep), tree)
    pshape = jax.eval_shape(
        lambda: entry.model_lib.init(jax.random.PRNGKey(0), mc))
    params = described(pshape)
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(pshape))
    eng = entry.new_engine(ctx, mesh)
    spec = eng.spec
    print(f"{ctx.cell['name']}: weights {held:,} B, paged pool "
          f"{spec.bytes - spec.window_bytes:,} B "
          f"({spec.pools} of {spec.pool_shape})", flush=True)
    # the state's own layout, whatever kinds of cache the engine keeps
    state = described(jax.eval_shape(eng.init_state))
    eng._note_program = lambda *a, **kw: None
    row = np.full((spec.max_pages_per_slot,), -1, np.int32)
    for name, call in (
            (f"prefill pad={e['prompt_pad']}", lambda: eng.prefill(
                params, state, np.zeros((1, e["prompt_pad"]), np.int32),
                1, 0, 2, page_row=row)),
            (f"decode k={e['decode_k']}", lambda: eng.decode(
                params, state, e["decode_k"]))):
        try:
            call()
        except cr.Lowered as ex:
            if a.text:
                os.makedirs(a.text, exist_ok=True)
                with open(os.path.join(a.text, name.split()[0] + ".txt"),
                          "w") as f:
                    f.write(ex.lowered.compile().as_text())
            cr.report(f"{ctx.cell['name']} {name}", ex.lowered)
    return 0


def limits(a) -> int:
    """Over many seeds in ONE process, the numbers that decide ``correct``:
    the program's, and on the first ``--control`` seeds the control's (the
    reference in fp8 put in the program's place), the witness's (the
    reference in the configuration's own bfloat16, where the cell's
    reference has that mode: what the precision alone costs, to be read
    beside the program's and expected correct) and each planted fault's,
    every one judged by the limits in the cell's own file."""
    from perfbench import run as run_mod
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    dev = None
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        ctx = ctx_for(a, seed, a.seconds)
        if a.layers:
            # a rung of the depth ladder: the cell's own widths, shapes and
            # traffic over fewer layers, program and reference alike
            ctx.config = dict(ctx.config, num_layers=a.layers)
        entry = entry_of(ctx)
        if dev is None:
            dev = run_mod.setup_jax(ctx)
        res = entry.window(ctx)
        alts = [("fp8", None), *getattr(entry, "WITNESS", ())] \
            + [(None, f) for f in entry.faults()] if n < a.control else []
        if a.only:
            alts = [v for v in alts if (v[1] or v[0]) in a.only.split(",")]
        got = entry.score(ctx, res["sample"], alts)
        row = {"workload": a.workload, "seed": seed, "device": dev,
               "layers": ctx.config.get("num_layers"),
               "e2e": res["e2e"], "failed": res["failed"], "correct": {}}
        for name, gaps in got.items():
            name = {"gaps": "program", "fp8": "control_fp8",
                    "bf16": "witness_bf16"}.get(name, "fault_" + name)
            c = entry.compared(ctx, gaps, res["failed"])
            row[name] = {k: v["value"] for k, v in c.items()}
            row[name]["logit_gap_max"] = float(gaps.max())
            row[name]["nonzero"] = int((gaps > 0).sum())
            row[name]["tokens"] = int(len(gaps))
            row["correct"][name] = all(v["value"] <= v["limit"]
                                       for v in c.values())
        print("LIMITS " + json.dumps(row), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tool", choices=("sweep", "compile", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", type=int, default=3,
                    help="limits: how many of the seeds also read the "
                         "control and the planted faults")
    ap.add_argument("--layers", type=int, default=0,
                    help="limits: the configuration at this depth instead "
                         "of its own (how a number grows with the layers)")
    ap.add_argument("--only", default="",
                    help="limits: read these of the variants alone (fp8, "
                         "bf16, a fault's name), comma-separated")
    ap.add_argument("--out", default="chiprun_out/limits_serve.jsonl")
    ap.add_argument("--text", help="compile: write the compiled HLO here")
    a = ap.parse_args()
    return {"sweep": sweep, "compile": compile_only, "limits": limits}[
        a.tool](a)


if __name__ == "__main__":
    sys.exit(main())
