"""A serve cell of the ``sdar_moe`` configuration: ``run_serve`` over a
``PagedServeEngine`` as in ``serve_entry`` (whose recorder and capture
window these are), with this configuration's own model config, engine,
FLOPs and reference. The model generates by diffusion over blocks: a
request's first tokens come back with its first block (the scheduler's
``serve_first_tokens`` record, taken here on the harness's clock), and
what is compared is every served token AT THE STEP it was unmasked. The
mix's token ids are drawn over the vocabulary without the mask token.

The program's model module is imported as this file is: a program that has
none (the parent commit) fails the cell there, in seconds, before anything
is allocated."""

from __future__ import annotations

import os
import time

import numpy as np

from perfbench.lib import flops_sdar as flops_lib
from perfbench.lib import stats
from perfbench.lib import traffic as traffic_lib
from perfbench.lib.serve_entry import CaptureWindow, Recorder
from tpudist.models import sdarmoe as model_lib  # noqa: F401

TRACE_SPANS = 1 << 19


def model_config(ctx):
    from tpudist.config import ModelConfig
    m, e = ctx.config, ctx.traffic["engine"]
    return ModelConfig(
        name="sdarmoe", vocab_size=m["vocab_size"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["moe_intermediate_size"], max_seq_len=e["max_seq"],
        rope_theta=float(m["rope_theta"]), n_experts=m["num_experts"],
        expert_top_k=m["num_experts_per_tok"], norm_eps=m["rms_norm_eps"],
        block_length=m["block_length"], denoise_steps=m["denoising_steps"],
        mask_token_id=m["mask_token_id"])


def build_engine(ctx):
    import jax.numpy as jnp

    from tpudist.config import ParallelConfig
    from tpudist.parallel.mesh import build_mesh
    from tpudist.serve.engine import PagedServeEngine, init_params
    e = ctx.traffic["engine"]
    mc = model_config(ctx)
    mesh = build_mesh(ParallelConfig())
    params = init_params(mc, mesh, seed=ctx.seed)
    engine = PagedServeEngine(
        mc, mesh, slots=e["slots"], max_seq=e["max_seq"],
        prompt_pad=e["prompt_pad"], page_tokens=e["page_tokens"],
        pages=e["pages"], speculate_k=0,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[e["dtype"]])
    engine.warmup(params)
    return engine, params


def requests_of(ctx, mix=None, seconds=None):
    """The mix's schedule with ids drawn over the vocabulary WITHOUT the
    mask token: over one id fewer, stepping over it."""
    from tpudist.serve import scheduler as sched
    mask = ctx.config["mask_token_id"]
    reqs = traffic_lib.serve_requests(
        mix or ctx.traffic, ctx.seed, seconds or ctx.seconds,
        ctx.config["vocab_size"] - 1, ctx.traffic["engine"]["prompt_pad"])
    for _, t, pl, _ in reqs:
        t[:pl][t[:pl] >= mask] += 1
    return reqs, [sched.Request(rid=i, arrival_s=a, tokens=t, prompt_len=pl,
                                max_new=mn)
                  for i, (a, t, pl, mn) in enumerate(reqs)]


def model_flops(ctx, reqs, results) -> float:
    """Model FLOPs of every real token the window processed, each once:
    each admitted prompt's tokens and each token emitted after it."""
    return sum(flops_lib.forward_flops(
        ctx.config, reqs[i][2] + res["generated"],
        reqs[i][2] + res["generated"]) for i, res in results.items())


def reduce_events(rec: Recorder, reqs) -> dict:
    """Per-request times on the harness's clock: the first tokens at the
    moment the scheduler reports a request's first block."""
    due = {i: rec.t0 + r[0] for i, r in enumerate(reqs)}
    first, first_n, done, gen, queue_wait = {}, {}, {}, {}, {}
    for e in rec.events:
        if e.get("kind") == "serve_first_tokens":
            first[e["rid"]], first_n[e["rid"]] = e["recv"], e["tokens"]
        elif e.get("kind") != "serve_request":
            continue
        elif e["event"] == "admitted":
            queue_wait[e["rid"]] = e["queue_wait_s"]
        elif e["event"] == "done":
            done[e["rid"]], gen[e["rid"]] = e["recv"], e["generated"]
    ttft = [(first[i] - due[i]) if i in first else float("inf")
            for i in due]
    tpot = [(done[i] - first[i]) / (gen[i] - first_n[i]) for i in done
            if i in first and gen[i] > first_n[i]]
    return {"ttft_s": ttft, "tpot_s": tpot, "done": done, "gen": gen,
            "queue_wait_s": list(queue_wait.values()), "due": due}


def window(ctx) -> dict:
    """Set-up, the measured window, and the sample that is compared."""
    from perfbench.lib import reference_sdar as ref_lib
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched

    job = ctx.traffic
    # 128 slots leave ~135 spans a dispatch (one ``decode_emit`` a slot):
    # a run of ~700 dispatches overruns the tracer's default ring, and the
    # spans the per-layer metrics read would be the run's tail only
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
    print(f"perfbench: {len(reqs)} requests offered over {ctx.seconds} s "
          f"({len(reqs) / ctx.seconds:.3f}/s), {len(ok)} completed in full, "
          f"window {t_end - t_start:.2f} s, {summary['dispatches']} block "
          f"dispatches of {summary['tokens_per_dispatch']} tokens (mean), "
          f"{summary['forwards_per_token']} forwards a token; arrivals were "
          f"taken up {1e3 * stats.median(late):.1f} ms (median) and "
          f"{1e3 * max(late):.1f} ms (worst) after they were due; prompts "
          f"hold {sum(q[2] for q in reqs)} real tokens of "
          f"{len(reqs) * len(reqs[0][1])} padded; at the peak "
          f"{summary['kv_pages_used_peak']}/{summary['kv_pages_total']} "
          f"pages, {summary['active_slots_peak']} slots; "
          f"{summary['moe_pairs_per_expert_mean']} pair(s) an expert a "
          f"layer a forward, {summary['moe_experts_hit_mean']} expert(s) "
          f"hit a layer a forward (dispatch means)", flush=True)
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
    sample = [ref_lib.request_of(
        reqs[i][1][:reqs[i][2]], results[i]["tokens"],
        results[i]["unmask_step"], results[i]["surplus"],
        ctx.config["block_length"]) for i in pick]
    return {"e2e": e2e, "view": view, "sample": sample,
            "attempted": len(reqs), "failed": len(reqs) - len(ok),
            "memory_peak": memory_peak}


def score(ctx, sample, variants=()) -> dict:
    """The reference over the sample, once the program's state is freed:
    every served token's two gaps, and under each ``(quant, fault)`` of
    ``variants`` the gaps of what THAT computation would have served."""
    from perfbench.lib import reference_sdar as ref_lib
    ref_lib.make_room()
    t0 = time.perf_counter()
    pad = -(-ctx.traffic["engine"]["max_seq"] // ref_lib.ROW_CHUNK) \
        * ref_lib.ROW_CHUNK
    out = ref_lib.served_gaps(ctx.seed, ctx.config, sample, pad, variants)
    print(f"perfbench: reference scored {len(out['gaps'])} served tokens "
          f"of {len(sample)} requests"
          + (f" and {len(variants)} variant(s)" if variants else "")
          + f" in {time.perf_counter() - t0:.1f} s", flush=True)
    return out


def compared(ctx, gaps, conf_gaps, failed: int) -> dict:
    """The MEAN gaps are what is compared, for the reason ``cmdaplus``'s
    entry gives: top-8 of 128 is discontinuous, and where bfloat16 and
    float32 choose another eighth expert at a near-tie one served token
    can lie far under the reference's best while nearly all are its first
    choice. The widest is printed."""
    print(f"perfbench: widest logit gap {float(gaps.max()):.4f}, "
          f"{int((gaps > 0).sum())} of {len(gaps)} served tokens not the "
          f"reference's first choice; {int((conf_gaps > 0).sum())} not "
          f"unmasked at the reference's most confident position (widest "
          f"confidence gap {float(conf_gaps.max()):.3e})", flush=True)
    lim = ctx.traffic["limits"]
    return {"logit_gap_mean": {"value": float(gaps.mean()),
                               "limit": lim["logit_gap_mean"]},
            "unmask_conf_gap_mean": {"value": float(conf_gaps.mean()),
                                     "limit": lim["unmask_conf_gap_mean"]},
            "requests_unfinished": {"value": failed, "limit": 0}}


def run(ctx) -> dict:
    res = window(ctx)
    got = score(ctx, res.pop("sample"))
    res["compared"] = compared(ctx, got["gaps"], got["conf_gaps"],
                               res["failed"])
    return res
