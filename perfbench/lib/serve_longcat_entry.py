"""A serve cell of the ``LongCat-Flash`` configuration: ``run_serve`` over a
``PagedServeEngine`` as in ``serve_entry`` (whose recorder, capture window
and reduction of events these are), with this configuration's own model
config, engine, FLOPs and reference. The mix's token ids are drawn over
the slice of the vocabulary held here.

The program's model module is imported as this file is: a program that has
none (the parent commit) fails the cell there, in seconds, before anything
is allocated."""

from __future__ import annotations

import os
import time

import numpy as np

from perfbench.lib import flops_longcatflash as flops_lib
from perfbench.lib import stats
from perfbench.lib import traffic as traffic_lib
from perfbench.lib.serve_entry import CaptureWindow, Recorder, reduce_events
from tpudist.models import longcatflash as model_lib  # noqa: F401

TRACE_SPANS = 1 << 19
# the reference in the configuration's own precision, for the cell's tools:
# what bfloat16 alone costs at this depth (no fault: expected correct)
WITNESS = (("bf16", None),)


def model_config(ctx):
    from tpudist.config import ModelConfig
    m, e = ctx.config, ctx.traffic["engine"]
    if m["zero_expert_type"] != "identity" or m["attention_method"] != "MLA":
        raise SystemExit("perfbench: the program builds identity zero "
                         "experts and MLA alone")
    return ModelConfig(
        name="longcatflash", vocab_size=m["vocab_size"],
        n_layers=m["num_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], d_ff=m["expert_ffn_hidden_size"],
        d_ff_dense=m["ffn_hidden_size"], max_seq_len=e["max_seq"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_experts=m["n_routed_experts_routed"],
        n_experts_held=m["n_routed_experts"],
        expert_first=m["expert_first"], n_zero_experts=m["zero_expert_num"],
        expert_top_k=m["moe_topk"],
        routed_scaling=float(m["routed_scaling_factor"]))


def new_engine(ctx, mesh):
    """The cell's engine over ``mesh``, nothing compiled yet (the cell's
    tools hand it a described chip's mesh)."""
    import jax.numpy as jnp

    from tpudist.serve.engine import PagedServeEngine
    e = ctx.traffic["engine"]
    return PagedServeEngine(
        model_config(ctx), mesh, slots=e["slots"], max_seq=e["max_seq"],
        prompt_pad=e["prompt_pad"], decode_k=e["decode_k"],
        page_tokens=e["page_tokens"], pages=e["pages"], speculate_k=0,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[e["dtype"]])


def build_engine(ctx):
    from tpudist.config import ParallelConfig
    from tpudist.parallel.mesh import build_mesh
    from tpudist.serve.engine import init_params
    mesh = build_mesh(ParallelConfig())
    params = init_params(model_config(ctx), mesh, seed=ctx.seed)
    engine = new_engine(ctx, mesh)
    engine.warmup(params)
    return engine, params


def requests_of(ctx, mix=None, seconds=None):
    from tpudist.serve import scheduler as sched
    reqs = traffic_lib.serve_requests(
        mix or ctx.traffic, ctx.seed, seconds or ctx.seconds,
        ctx.config["vocab_size"], ctx.traffic["engine"]["prompt_pad"])
    return reqs, [sched.Request(rid=i, arrival_s=a, tokens=t, prompt_len=pl,
                                max_new=mn)
                  for i, (a, t, pl, mn) in enumerate(reqs)]


def model_flops(ctx, reqs, results) -> float:
    """Model FLOPs of every real token the window processed: each admitted
    prompt, and each token decoded after it."""
    total = 0.0
    for i, res in results.items():
        pl = reqs[i][2]
        total += flops_lib.forward_flops(ctx.config, pl, pl)
        for j in range(1, res["generated"]):
            total += flops_lib.forward_flops(ctx.config, 1, pl + j)
    return total


def window(ctx) -> dict:
    """Set-up, the measured window, and the sample that is compared."""
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched

    job = ctx.traffic
    # 192 slots leave ~200 spans a dispatch (one ``decode_emit`` a slot):
    # the run's ~300 dispatches overrun the tracer's default ring
    tracer = trace_lib.configure(enabled=True, capacity=TRACE_SPANS)
    engine, params = build_engine(ctx)
    reqs, requests = requests_of(ctx)
    rec = Recorder()
    cap = None
    capture_dir = os.path.join(ctx.workdir, "capture")
    if ctx.trace:
        cap = CaptureWindow(
            engine, capture_dir,
            lambda: time.perf_counter() - (rec.t0 or float("inf")),
            job["capture_open_share"] * ctx.seconds,
            job["capture_dispatches"])
    if ctx.fault == "token_altered":
        inner = engine.decode

        def altered(*a, **kw):
            st, toks, valid = inner(*a, **kw)
            return st, (toks + 1) % ctx.config["vocab_size"], valid
        engine.decode = altered
    t_start = time.perf_counter()
    ctx.note_window(t_start)
    ctx.arm_compile_count(True)
    try:
        summary = sched.run_serve(engine, params, requests, metrics=rec,
                                  clock=rec.clock)
    finally:
        t_end = time.perf_counter()
        ctx.arm_compile_count(False)
        if cap is not None:
            cap.close()
    engine.assert_two_programs()
    memory_peak = ctx.memory_peak_bytes()
    r = reduce_events(rec, reqs)
    results = summary["results"]
    ok = [i for i, res in results.items() if res["why"] == "done"
          and res["generated"] == reqs[i][3]]
    out_tokens = sum(results[i]["generated"] for i in ok)
    last_done = max(r["done"].values()) if r["done"] else t_end
    span_s = last_done - min(r["due"].values())
    late = [s["ts"] / 1e6 - rec.t0 - s["args"]["arrival_s"]
            for s in tracer.events() if s["name"] == "arrive"]
    real = sum(q[2] for q in reqs)
    print(f"perfbench: {len(reqs)} requests offered over {ctx.seconds} s "
          f"({len(reqs) / ctx.seconds:.3f}/s), {len(ok)} completed in full, "
          f"window {t_end - t_start:.2f} s, {summary['dispatches']} decode "
          f"dispatches; arrivals were taken up "
          f"{1e3 * stats.median(late):.1f} ms (median) and "
          f"{1e3 * max(late):.1f} ms (worst) after they were due; prompts "
          f"hold {real} real tokens of {len(reqs) * len(reqs[0][1])} padded "
          f"({len(reqs) * len(reqs[0][1]) / real:.2f} x); at the peak "
          f"{summary['kv_pages_used_peak']}/{summary['kv_pages_total']} "
          f"pages of latent rows, {summary['active_slots_peak']} slots; "
          f"{summary['moe_pairs_per_expert_mean']} pair(s) an expert a "
          f"layer a token step, {summary['moe_experts_hit_mean']} expert(s) "
          f"hit a layer a step, {summary['moe_zero_share']} of all pairs on "
          f"identity experts (decode means)", flush=True)
    e2e = {"ttft_p95_ms": stats.percentile(r["ttft_s"], 95) * 1e3,
           "serve_tokens_per_s": out_tokens / span_s / ctx.chips}
    print(f"perfbench: ttft p50/p95 {1e3 * stats.median(r['ttft_s']):.0f}/"
          f"{e2e['ttft_p95_ms']:.0f} ms, tpot p50 "
          f"{1e3 * stats.median(r['tpot_s']) if r['tpot_s'] else 0:.1f} ms, "
          f"{e2e['serve_tokens_per_s']:.1f} tokens/s completed", flush=True)
    spans = [{"name": s["name"], "t0_us": s["ts"],
              "t1_us": s["ts"] + s["dur"], "args": s.get("args", {})}
             for s in tracer.events()]
    view = {"kind": "serve", "spans": spans, "events": rec.events,
            "window_us": (t_start * 1e6, t_end * 1e6),
            "wall_s": span_s, "chips": ctx.chips,
            "model_flops": model_flops(ctx, reqs, results),
            "config": ctx.config, "job": job,
            "queue_wait_s": r["queue_wait_s"], "tpot_s": r["tpot_s"],
            "capture_dir": capture_dir if ctx.trace else None,
            "capture_stretch_us": (cap.first_us, cap.last_us) if cap
            else None}
    # the sample that is compared: drawn from the seed among the requests
    # finished in full, the longest always in it
    rng = np.random.default_rng([ctx.seed, 41])
    longest = max(ok, key=lambda i: reqs[i][2] + reqs[i][3])
    pick = [longest] + [int(i) for i in rng.permutation(
        [i for i in ok if i != longest])[:job["check_requests"] - 1]]
    sample = [(reqs[i][1][:reqs[i][2]].copy(),
               np.asarray(results[i]["tokens"], np.int32)) for i in pick]
    return {"e2e": e2e, "view": view, "sample": sample,
            "attempted": len(reqs), "failed": len(reqs) - len(ok),
            "memory_peak": memory_peak}


def score(ctx, sample, variants=()) -> dict:
    """The reference over the sample, once the program's state is freed:
    every served token's gap, and under each ``(quant, fault)`` of
    ``variants`` the gap of what THAT computation would have served."""
    from perfbench.lib import reference_longcatflash as ref_lib
    ref_lib.make_room()
    t0 = time.perf_counter()
    out = ref_lib.served_gaps(ctx.seed, ctx.config, sample,
                              ctx.traffic["engine"]["max_seq"], variants)
    print(f"perfbench: reference scored {len(out['gaps'])} served tokens "
          f"of {len(sample)} requests"
          + (f" and {len(variants)} variant(s)" if variants else "")
          + f" in {time.perf_counter() - t0:.1f} s", flush=True)
    return out


def faults():
    """The planted faults the reference knows, for the cell's tools."""
    from perfbench.lib import reference_longcatflash as ref_lib
    return ref_lib.FAULTS


def compared(ctx, gaps, failed: int) -> dict:
    """The MEAN gap is what is compared, for the reason ``cmdaplus``'s
    entry gives: top-12 of 768 is discontinuous, so the widest gap swings.
    In this cell the mean sits on bfloat16's own floor: at the cell's size
    four of five served tokens are NOT the float32 reference's first
    choice (near-argmax attention through eight sublayers; the reference's
    own bfloat16 witness, ``WITNESS``, reads the same), and the limit parts
    that floor from the control and the planted faults above it. The
    widest gap and the count are printed."""
    print(f"perfbench: widest gap {float(gaps.max()):.4f}, "
          f"{int((gaps > 0).sum())} of {len(gaps)} served tokens not the "
          f"reference's first choice", flush=True)
    return {"logit_gap_mean": {"value": float(gaps.mean()),
                               "limit": ctx.traffic["limits"]
                               ["logit_gap_mean"]},
            "requests_unfinished": {"value": failed, "limit": 0}}


def run(ctx) -> dict:
    res = window(ctx)
    gaps = score(ctx, res.pop("sample"))["gaps"]
    res["compared"] = compared(ctx, gaps, res["failed"])
    return res
