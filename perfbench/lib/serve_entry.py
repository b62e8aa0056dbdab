"""A serve cell: the window drives ``tpudist.serve.scheduler.run_serve``
over a ``PagedServeEngine``, with requests from the benchmark's own seeded
generator, the benchmark's own clock handed in as ``clock=``, and the
benchmark's own recorder handed in as ``metrics=``. Latencies are taken on
the harness's clock at the moment the scheduler reports each event (an
admission is reported right after the fenced prefill that produced the
first token; a completion right after the fenced dispatch that produced
the last), not from the program's arithmetic."""

from __future__ import annotations

import os
import time

import numpy as np

from perfbench.lib import flops as flops_lib
from perfbench.lib import stats
from perfbench.lib import traffic as traffic_lib


class Recorder:
    """What ``run_serve`` is given as ``metrics`` and as ``clock``."""

    def __init__(self):
        self.t0 = None
        self.events = []

    def clock(self) -> float:
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = t          # run_serve's first read is its time zero
        return t

    def log(self, **kw) -> None:
        kw["recv"] = time.perf_counter()
        self.events.append(kw)

    def flush(self) -> None:
        pass


class CaptureWindow:
    """Traced runs only: wraps ``engine.decode``. Once ``open_at_s`` of the
    arrival period has passed it opens a profiler session and stamps the
    host clock before the next dispatch and after the fence of the
    ``n``-th: that stretch, with the admissions and prefills that fall in
    it, is what the capture is cut to. The session stays open until
    ``run_serve`` has returned: closing one stalls its caller for seconds
    (8.5 s after 12 dispatches in my chip runs, PR 23), which inside the
    loop would hold every arrival behind it, so the close is paid outside
    the window."""

    def __init__(self, engine, out_dir: str, clock, open_at_s: float,
                 n: int):
        self.inner, self.dir = engine.decode, out_dir
        self.clock, self.open_at_s, self.n = clock, open_at_s, n
        self.seen = 0
        # host clock before the first captured dispatch and after the last,
        # microseconds; the end stays None where the run ended first
        self.first_us = self.last_us = None
        engine.decode = self

    def __call__(self, *a, **kw):
        import jax
        if self.first_us is None and self.clock() >= self.open_at_s:
            os.makedirs(self.dir, exist_ok=True)
            # device and runtime events only: with the Python tracer on,
            # every call of the scheduler's loop is an event
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.first_us = time.perf_counter() * 1e6
        out = self.inner(*a, **kw)
        if self.first_us is not None and self.seen < self.n:
            self.seen += 1
            if self.seen == self.n:
                jax.block_until_ready(out[1])   # the scheduler's own fence
                self.last_us = time.perf_counter() * 1e6   # comes next
        return out

    def close(self):
        """Once, after ``run_serve`` has returned."""
        if self.first_us is not None:
            import jax
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            print(f"perfbench: the capture, cut to {self.seen} dispatches, "
                  f"closed in {time.perf_counter() - t0:.2f} s after the "
                  f"window", flush=True)


def model_config(ctx):
    from tpudist.config import ModelConfig
    m, job = ctx.config, ctx.traffic
    return ModelConfig(
        name="transformer", vocab_size=m["vocab_size"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        max_seq_len=job["engine"]["max_seq"],
        rope_theta=float(m["rope_theta"]))


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
        prompt_pad=e["prompt_pad"], decode_k=e["decode_k"],
        page_tokens=e["page_tokens"], pages=e["pages"], speculate_k=0,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[e["dtype"]])
    engine.warmup(params)
    return engine, params


def reduce_events(rec: Recorder, reqs) -> dict:
    """Per-request times on the harness's clock."""
    due = {i: rec.t0 + r[0] for i, r in enumerate(reqs)}
    first, done, gen, queue_wait = {}, {}, {}, {}
    for e in rec.events:
        if e.get("kind") != "serve_request":
            continue
        if e["event"] == "admitted":
            first[e["rid"]] = e["recv"]
            queue_wait[e["rid"]] = e["queue_wait_s"]
        elif e["event"] == "done":
            done[e["rid"]] = e["recv"]
            gen[e["rid"]] = e["generated"]
    worst = float("inf")
    ttft = [(first[i] - due[i]) if i in first else worst for i in due]
    tpot = [(done[i] - first[i]) / (gen[i] - 1) for i in done
            if gen[i] >= 2]
    return {"ttft_s": ttft, "tpot_s": tpot, "done": done, "gen": gen,
            "queue_wait_s": list(queue_wait.values()), "due": due}


def window(ctx) -> dict:
    """Set-up, the measured window, and the sample that is compared."""
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched

    job = ctx.traffic
    e = job["engine"]
    tracer = trace_lib.configure(enabled=True)
    engine, params = build_engine(ctx)
    reqs = traffic_lib.serve_requests(job, ctx.seed, ctx.seconds,
                                      ctx.config["vocab_size"],
                                      e["prompt_pad"])
    requests = [sched.Request(rid=i, arrival_s=a, tokens=t, prompt_len=pl,
                              max_new=mn)
                for i, (a, t, pl, mn) in enumerate(reqs)]
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
    memory_peak = ctx.memory_peak_bytes()
    r = reduce_events(rec, reqs)
    results = summary["results"]
    ok = [i for i, res in results.items() if res["why"] == "done"
          and res["generated"] == reqs[i][3]]
    out_tokens = sum(results[i]["generated"] for i in ok)
    last_done = max(r["done"].values()) if r["done"] else t_end
    first_due = min(r["due"].values())
    span_s = last_done - first_due
    late = [s["ts"] / 1e6 - rec.t0 - s["args"]["arrival_s"]
            for s in tracer.events() if s["name"] == "arrive"]
    print(f"perfbench: {len(reqs)} requests offered over {ctx.seconds} s "
          f"({len(reqs) / ctx.seconds:.3f}/s), {len(ok)} completed in full, "
          f"window {t_end - t_start:.2f} s, {summary['dispatches']} decode "
          f"dispatches; arrivals were taken up "
          f"{1e3 * stats.median(late):.1f} ms (median) and "
          f"{1e3 * max(late):.1f} ms (worst) after they were due",
          flush=True)
    # the tail a p95 is read from: which request holds the rank, and how
    # far its neighbours lie (a flip of one across the rank is the spread)
    rids = [i for i in r["done"] if r["gen"][i] >= 2]
    tail = sorted(zip(r["tpot_s"], rids), reverse=True)[:12]
    print("perfbench: worst tpot, rid:generated:ms " + " ".join(
        f"{i}:{r['gen'][i]}:{1e3 * v:.2f}" for v, i in tail), flush=True)
    tail = sorted(zip(r["ttft_s"], r["due"]), reverse=True)[:12]
    print("perfbench: worst ttft, rid:ms " + " ".join(
        f"{i}:{1e3 * v:.1f}" for v, i in tail), flush=True)
    e2e = {"ttft_p95_ms": stats.percentile(r["ttft_s"], 95) * 1e3,
           "serve_tokens_per_s": out_tokens / span_s / ctx.chips}
    if r["tpot_s"]:
        e2e["tpot_p95_ms"] = stats.percentile(r["tpot_s"], 95) * 1e3
    model_flops = 0.0
    for i, res in results.items():
        pl = reqs[i][2]
        model_flops += flops_lib.forward_flops(ctx.config, pl, pl)
        for j in range(1, res["generated"]):
            model_flops += flops_lib.forward_flops(ctx.config, 1, pl + j)
    spans = [{"name": s["name"], "t0_us": s["ts"],
              "t1_us": s["ts"] + s["dur"], "args": s.get("args", {})}
             for s in tracer.events()]
    view = {"kind": "serve", "spans": spans, "events": rec.events,
            "window_us": (t_start * 1e6, t_end * 1e6),
            "wall_s": span_s, "chips": ctx.chips,
            "model_flops": model_flops, "config": ctx.config, "job": job,
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


def score(ctx, sample, mode=None) -> dict:
    """The reference over the sample, once the program's state is freed:
    every served token's gap, and with ``mode`` the control's."""
    from perfbench.lib import reference as ref_lib
    ref_lib.make_room()
    t0 = time.perf_counter()
    ref_params = ref_lib.init_params(ctx.seed, ctx.config)
    got = [ref_lib.served_gaps(ref_params, p, s, ctx.config,
                               ctx.traffic["engine"]["max_seq"], mode)
           for p, s in sample]
    out = {k: np.concatenate([g[k] for g in got]) for k in got[0]}
    print(f"perfbench: reference scored {len(out['gaps'])} served tokens "
          f"of {len(sample)} requests in {time.perf_counter() - t0:.1f} s",
          flush=True)
    return out


def run(ctx) -> dict:
    res = window(ctx)
    gaps = score(ctx, res.pop("sample"))["gaps"]
    res["compared"] = {
        "logit_gap_max": {"value": float(gaps.max()),
                          "limit": ctx.traffic["limits"]["logit_gap_max"]},
        "requests_unfinished": {"value": res["failed"], "limit": 0}}
    return res
