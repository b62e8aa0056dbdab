"""The tools of a cell whose entry is ``serve_sdar`` (``cell_cohere2moe``'s
three, for this entry's engine, state and comparison): the sweep for the
knee, the compile-only sizing and the judging of control and planted
faults, by the cell's name.

    python3 perfbench/tools/cell_sdar.py sweep --workload W --rates 4,6,8 --seconds 30
    JAX_PLATFORMS=cpu python3 perfbench/tools/cell_sdar.py compile --workload W
    python3 perfbench/tools/cell_sdar.py limits --workload W --seeds 1,2,3 [--control 3]

``sweep`` and ``limits`` run on the chip at the cell's own size, in ONE
process each; ``compile`` runs here, for no chip time, and what it prints
is "compiled, not run".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.tools.cell_cohere2moe import ctx_for      # noqa: E402


def sweep(a) -> int:
    """One engine, the cell's own length mix offered at each rate for
    ``--seconds``: the time to first tokens of the first and last third of
    the requests (a backlog that grows shows as a last third far above the
    first), how long the queue took to drain, completed tokens a second.
    The knee is written into the cell's traffic file by hand."""
    from perfbench import run as run_mod
    from perfbench.lib import serve_sdar_entry as entry
    from perfbench.lib import stats
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched
    ctx = ctx_for(a, a.seed, a.seconds)
    t0 = time.perf_counter()
    dev = run_mod.setup_jax(ctx)
    engine, params = entry.build_engine(ctx)
    print(f"SWEEP set-up {time.perf_counter() - t0:.1f} s, memory peak "
          f"{ctx.memory_peak_bytes():,} B", flush=True)
    for rate in (float(r) for r in a.rates.split(",")):
        tracer = trace_lib.configure(enabled=True,
                                     capacity=entry.TRACE_SPANS)
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
        steps = [s["dur"] / 1e3 for s in tracer.events()
                 if s["name"] == "decode_step"]
        pre = [s["dur"] / 1e3 for s in tracer.events()
               if s["name"] == "prefill"]
        print("SWEEP " + json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "completed": summary["completed"], "wall_s": wall,
            "drain_s": wall - a.seconds,
            "ttft_first_third_p50_ms": 1e3 * stats.median(ttft[:third]),
            "ttft_last_third_p50_ms": 1e3 * stats.median(ttft[-third:]),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * stats.median(r["tpot_s"]),
            "tokens_per_s": sum(r["gen"].values()) / wall,
            "dispatches": summary["dispatches"],
            "dispatch_ms_p50": stats.median(steps),
            "prefill_ms_p50": stats.median(pre),
            "active_slots_peak": summary["active_slots_peak"],
            "kv_pages_used_peak": summary["kv_pages_used_peak"],
            "memory_peak_bytes": ctx.memory_peak_bytes(),
            "device": dev}), flush=True)
    return 0


def compile_only(a) -> int:
    """The cell's prefill and denoising programs lowered for a described
    ``v5e:2x2`` at the real sizes with the chip's own compiler."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from perfbench.lib import serve_sdar_entry as entry
    from perfbench.tools import compile_rehearsal as cr
    from tpudist.serve.engine import PagedServeEngine, PagedServeState
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cr.steer()
    ctx = ctx_for(a, 1, 10)
    e = ctx.traffic["engine"]
    mc = entry.model_config(ctx)
    mesh = cr.mesh_for(topo, ctx.chips)
    rep = NamedSharding(mesh, P())
    pshape = jax.eval_shape(
        lambda: entry.model_lib.init(jax.random.PRNGKey(0), mc))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=rep), pshape)
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(pshape))
    eng = PagedServeEngine(
        mc, mesh, slots=e["slots"], max_seq=e["max_seq"],
        prompt_pad=e["prompt_pad"], page_tokens=e["page_tokens"],
        pages=e["pages"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[e["dtype"]])
    spec, s, b = eng.spec, eng.slots, eng.block
    print(f"{ctx.cell['name']}: weights {held:,} B, pool {spec.bytes:,} B",
          flush=True)
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    state = PagedServeState(
        arr(spec.pool_shape, eng.dtype), arr(spec.pool_shape, eng.dtype),
        arr((s,), jnp.int32), arr((s,), jnp.int32), arr((s,), jnp.bool_),
        arr((s,), jnp.int32), (), (), arr((3,), jnp.int32),
        arr((s, b), jnp.int32), arr((s, b), jnp.bool_),
        arr((s, b), jnp.int32))
    eng._note_program = lambda *a, **kw: None
    row = np.full((spec.max_pages_per_slot,), -1, np.int32)
    for name, call in (
            (f"prefill pad={e['prompt_pad']}", lambda: eng.prefill(
                params, state, np.zeros((1, e["prompt_pad"]), np.int32),
                1, 0, 2, page_row=row)),
            (f"denoise block={b}", lambda: eng.decode(params, state))):
        t0 = time.perf_counter()
        try:
            call()
        except cr.Lowered as ex:
            if a.text:
                os.makedirs(a.text, exist_ok=True)
                with open(os.path.join(a.text, name.split()[0] + ".txt"),
                          "w") as f:
                    f.write(ex.lowered.compile().as_text())
            cr.report(f"{ctx.cell['name']} {name}", ex.lowered)
        print(f"{name}: traced, lowered and compiled here in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    return 0


def limits(a) -> int:
    """Over many seeds in ONE process, the numbers that decide ``correct``:
    the program's, and on the first ``--control`` seeds the control's (the
    reference in fp8 put in the program's place) and each planted fault's,
    on every seed the planted orders of unmasking (the reference's own
    rows, read again), every one judged by the limits in the cell's own
    file."""
    from perfbench import run as run_mod
    from perfbench.lib import reference_sdar as ref_lib
    from perfbench.lib import serve_sdar_entry as entry
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    dev = None
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        ctx = ctx_for(a, seed, a.seconds)
        if dev is None:
            dev = run_mod.setup_jax(ctx)
        res = entry.window(ctx)
        # the planted orders cost no forward: on every seed
        alts = [(None, f) for f in ref_lib.ORDER_FAULTS]
        if n < a.control:
            alts += [("fp8", None)] + [(None, f) for f in ref_lib.FAULTS]
        got = entry.score(ctx, res["sample"], alts)
        row = {"workload": a.workload, "seed": seed, "device": dev,
               "e2e": res["e2e"], "failed": res["failed"],
               "memory_peak_bytes": res["memory_peak"], "correct": {}}
        for name in [""] + [f or m for m, f in alts]:
            pre = name + "_" if name else ""
            gaps, conf = got[pre + "gaps"], got[pre + "conf_gaps"]
            c = entry.compared(ctx, gaps, conf, res["failed"])
            name = {"": "program", "fp8": "control_fp8"}.get(
                name, "fault_" + name)
            row[name] = {k: v["value"] for k, v in c.items()}
            row[name].update(logit_gap_max=float(gaps.max()),
                             nonzero=int((gaps > 0).sum()),
                             conf_nonzero=int((conf > 0).sum()),
                             tokens=int(len(gaps)))
            row["correct"][name] = all(v["value"] <= v["limit"]
                                       for v in c.values())
        print("LIMITS " + json.dumps(row), flush=True)
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tool", choices=("sweep", "compile", "limits"))
    ap.add_argument("--workload", default="sdar-serve-longgen-sat")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", type=int, default=3,
                    help="limits: how many of the seeds also read the "
                         "control and the planted faults")
    ap.add_argument("--out", default="chiprun_out/limits_sdar.jsonl")
    ap.add_argument("--text", help="compile: write the compiled HLO here")
    a = ap.parse_args()
    return {"sweep": sweep, "compile": compile_only, "limits": limits}[
        a.tool](a)


if __name__ == "__main__":
    sys.exit(main())
