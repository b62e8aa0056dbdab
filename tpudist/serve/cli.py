"""``python -m tpudist.serve`` — the serving acceptance lane.

One command drives the whole serve stack end to end on whatever mesh
the platform gives it (the scripted CPU mesh in CI, a pod slice under
``launch_tpu.sh MODE=serve``): build the model and its sharded KV
cache, warm the compiled programs (one prefill + one decode per adapt
rung), optionally let the serve autotuner pick ``decode_k``, the page
size and the speculation window by measured probe, run the
continuous-batching loop — with admission control, deadline shedding
and graceful degradation when the resilience knobs are on
(:mod:`tpudist.serve.resilience`) — over a seeded Poisson request
stream, and grade the latency SLOs plus the shed gate. Under the launcher's requeue loop (``--requeue-attempt``),
a restarted attempt replays the still-live requests from the seeded
schedule and classifies the dead attempt's in-flight slots as lost.

Artifacts mirror the train lane's: ``metrics.jsonl`` (``kind=serve`` /
``serve_tick`` / ``serve_tune`` / per-request ``serve_request``
records) under ``--save-dir``, the span trace — on by default, same
``--trace``/``TPUDIST_TRACE`` resolution as training — exported as
``trace.worker<i>.json`` plus the merged ``pod_trace.json`` with
per-request flight timelines, per-slot tracks and a KV-pool occupancy
counter track (verify offline with ``python -m tpudist.serve.flight``),
the run summary as one JSON document (``--bench-out``),
a Prometheus exporter while the run lives (``--live-port``), and the
machine-readable verdict file (``TPUDIST_VERDICT_PATH``) carrying the
three-valued SLO verdict. Exit code: 0 unless an SLO gate FAILED — an
ungateable run (nothing measured) is not a latency regression.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, Optional, Sequence

from tpudist.serve import slo as slo_lib

DEFAULT_SLOTS = 4
DEFAULT_MAX_SEQ = 64
DEFAULT_PROMPT_PAD = 16
DEFAULT_DECODE_K = 8
DEFAULT_KV_PAGE_TOKENS = 64    # the page of every chip run on the ledger


def parse_args(argv: Optional[Sequence[str]] = None
               ) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="python -m tpudist.serve",
        description="tpudist serving acceptance lane: continuous "
                    "batching + sharded KV cache + latency-SLO verdict")
    p.add_argument("--model", choices=("transformer", "moe", "cohere2moe",
                                       "sdarmoe", "longcatflash"),
                   default="transformer",
                   help="cohere2moe: parallel block, window and NoPE-full "
                        "attention by layer, sigmoid-routed experts of "
                        "which --n-experts-held live here, averaged "
                        "shared experts; weights at rest in bfloat16. "
                        "sdarmoe: generation by diffusion over blocks of "
                        "4 (the mask token is the vocabulary's last id), "
                        "softmax-routed experts all held here, q/k norm, "
                        "untied head. longcatflash: latent attention over "
                        "a latent paged cache (--kv-lora-rank and the "
                        "head widths below), two attention sublayers and "
                        "two dense FFNs of --d-ff-dense a layer around "
                        "one mix of --n-experts real experts of --d-ff "
                        "(--n-experts-held here) and --n-zero-experts "
                        "identity ones, untied head")
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-kv-heads", type=int, default=2,
                   help="GQA: compact kv heads stored in the cache")
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--n-experts", type=int, default=4)
    p.add_argument("--expert-top-k", type=int, default=2)
    p.add_argument("--head-dim", type=int, default=0,
                   help="cohere2moe: width of a head (0: d-model/n-heads)")
    p.add_argument("--sliding-window", type=int, default=0,
                   help="cohere2moe: keys a window layer's query sees, "
                        "its own included (3 of every 4 layers)")
    p.add_argument("--n-experts-held", type=int, default=0,
                   help="cohere2moe: experts 0..n-1 live here, the "
                        "router stays --n-experts wide (0: all)")
    p.add_argument("--n-shared-experts", type=int, default=0,
                   help="cohere2moe: shared experts, averaged")
    p.add_argument("--q-lora-rank", type=int, default=0,
                   help="longcatflash: width of the query's latent")
    p.add_argument("--kv-lora-rank", type=int, default=0,
                   help="longcatflash: width of the cached latent")
    p.add_argument("--qk-nope-head-dim", type=int, default=0,
                   help="longcatflash: a head's query/key part without "
                        "rope")
    p.add_argument("--qk-rope-head-dim", type=int, default=0,
                   help="longcatflash: ... and with (one key for all "
                        "heads)")
    p.add_argument("--v-head-dim", type=int, default=0,
                   help="longcatflash: a head's value")
    p.add_argument("--n-zero-experts", type=int, default=0,
                   help="longcatflash: identity experts the router also "
                        "scores")
    p.add_argument("--d-ff-dense", type=int, default=0,
                   help="longcatflash: width of the two dense FFNs a layer")
    p.add_argument("--slots", type=int, default=DEFAULT_SLOTS,
                   help="concurrent sequences (KV cache pages)")
    p.add_argument("--max-seq", type=int, default=DEFAULT_MAX_SEQ,
                   help="per-slot cache page length")
    p.add_argument("--prompt-pad", type=int, default=DEFAULT_PROMPT_PAD,
                   help="static prompt width every admission pads to "
                        "(one compiled prefill program)")
    p.add_argument("--decode-steps-per-dispatch", type=int,
                   default=DEFAULT_DECODE_K, dest="decode_k",
                   help="decode superstep length (tokens per dispatch "
                        "per slot)")
    # ---- the KV pool (tpudist.serve.kvcache) ----
    p.add_argument("--kv-page-tokens", type=_page_tokens,
                   default=_env_int("TPUDIST_SERVE_KV_PAGE_TOKENS")
                   or DEFAULT_KV_PAGE_TOKENS,
                   help="KV cache page length in positions, >= 1; held "
                        "to at most --max-seq "
                        "($TPUDIST_SERVE_KV_PAGE_TOKENS)")
    p.add_argument("--kv-pages", type=int,
                   default=_env_int("TPUDIST_SERVE_KV_PAGES") or 0,
                   help="pool size in pages (+1 trash page is added "
                        "internally); 0 = full capacity "
                        "slots*ceil(max_seq/page_tokens) "
                        "($TPUDIST_SERVE_KV_PAGES)")
    p.add_argument("--shared-prefix", type=int,
                   default=_env_int("TPUDIST_SERVE_SHARED_PREFIX")
                   or 0,
                   help="every request starts with this many shared "
                        "system-prompt tokens; the engine stores "
                        "their full pages ONCE (refcounted, "
                        "copy-on-write fork of the partial tail) "
                        "($TPUDIST_SERVE_SHARED_PREFIX)")
    p.add_argument("--speculate-k", type=int,
                   default=_env_int("TPUDIST_SERVE_SPECULATE_K") or 0,
                   help="speculative decoding verify-window width: "
                        "last token + k-1 n-gram draft tokens scored "
                        "in ONE batched target forward; 0 = off "
                        "($TPUDIST_SERVE_SPECULATE_K)")
    p.add_argument("--requests", type=int, default=32,
                   help="synthetic request count")
    p.add_argument("--request-rate", type=float, default=0.0,
                   help="Poisson arrival rate in requests/s "
                        "(<= 0: closed loop, all present at t=0)")
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    # ---- the resilience plane (tpudist.serve.resilience) ----
    p.add_argument("--queue-cap", type=int,
                   default=_env_int("TPUDIST_SERVE_QUEUE_CAP") or 0,
                   help="bounded admission queue: arrivals past this "
                        "many waiting requests are SHED "
                        "($TPUDIST_SERVE_QUEUE_CAP; 0 = unbounded)")
    p.add_argument("--ttft-deadline-ms", type=float,
                   default=_env_float("TPUDIST_SERVE_TTFT_DEADLINE_MS")
                   or 0.0,
                   help="per-request TTFT deadline: accepted requests "
                        "still queued past this age are EXPIRED "
                        "($TPUDIST_SERVE_TTFT_DEADLINE_MS; 0 = off)")
    p.add_argument("--adapt", choices=("off", "on"),
                   default=os.environ.get("TPUDIST_SERVE_ADAPT", "off"),
                   help="graceful degradation: downshift decode_k on "
                        "the pre-compiled ladder when rolling queue "
                        "depth/ITL crosses the pressure thresholds, "
                        "restore when it clears ($TPUDIST_SERVE_ADAPT)")
    p.add_argument("--adapt-max-new-cap", type=int, default=0,
                   help="under degradation, truncate admitted "
                        "requests' generation budget to this many "
                        "tokens (0 = no truncation)")
    p.add_argument("--requeue-attempt", type=int, default=None,
                   help="requeue loop attempt index (the launcher "
                        "passes it whenever MAX_REQUEUES > 0): its "
                        "PRESENCE arms supervision — per-request "
                        "outcome events get boundary flushes so a "
                        "preemption cannot eat them — and attempt > 0 "
                        "replays the seeded stream MINUS requests a "
                        "prior attempt already finished, classifying "
                        "its in-flight slots as lost")
    p.add_argument("--chaos", type=str,
                   default=os.environ.get("TPUDIST_CHAOS"),
                   help="scripted serve-surface fault plan "
                        "(tpudist.chaos: serve_kill@0:<dispatch>, "
                        "serve_slow, request_garbage; $TPUDIST_CHAOS)")
    p.add_argument("--virtual-clock", action="store_true",
                   default=os.environ.get(
                       "TPUDIST_SERVE_VIRTUAL_CLOCK", "").lower()
                   in ("on", "1", "true"),
                   help="deterministic drill mode: the request clock "
                        "advances by scripted per-prefill/per-dispatch "
                        "costs instead of wall time — two runs of one "
                        "seed produce bitwise-identical SLO summaries "
                        "($TPUDIST_SERVE_VIRTUAL_CLOCK)")
    p.add_argument("--virtual-prefill-ms", type=float, default=2.0)
    p.add_argument("--virtual-decode-ms", type=float, default=4.0)
    p.add_argument("--serve-tune", choices=("off", "probe", "cache-only"),
                   default=os.environ.get("TPUDIST_SERVE_TUNE", "off"),
                   help="autotune decode_k, the page size and the "
                        "speculation window by measured probe "
                        "(tpudist.serve.tune; $TPUDIST_SERVE_TUNE)")
    p.add_argument("--tune-cache-dir", type=str, default=None,
                   help="serve tuner cache dir (default "
                        "$TPUDIST_AUTOTUNE_CACHE_DIR, else "
                        "<save-dir>/tune — shared with the train tuner, "
                        "distinct file prefix)")
    p.add_argument("--save-dir", type=str, default="ckpt",
                   help="metrics.jsonl destination")
    p.add_argument("--bench-out", type=str, default=None,
                   help="write the run summary as one JSON document "
                        "here")
    p.add_argument("--trace", choices=("on", "off"), default=None,
                   help="span tracing (request flight timelines + KV "
                        "occupancy counters); default on — same "
                        "resolution as the train lane: flag > "
                        "$TPUDIST_TRACE > on")
    p.add_argument("--trace-dir", type=str,
                   default=os.environ.get("TPUDIST_TRACE_DIR"),
                   help="span-trace export dir ($TPUDIST_TRACE_DIR, "
                        "else --save-dir)")
    p.add_argument("--live-port", type=int, default=_env_int(
        "TPUDIST_LIVE_PORT"),
        help="serve Prometheus /metrics + /status.json on this port "
             "while the run lives ($TPUDIST_LIVE_PORT)")
    return p.parse_args(argv)


def _page_tokens(raw: str) -> int:
    n = int(raw)
    if n < 1:
        raise argparse.ArgumentTypeError(
            f"a page holds at least one position, got {n}")
    return n


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name)
    try:
        return int(raw) if raw else None
    except ValueError:
        return None


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name)
    try:
        return float(raw) if raw else None
    except ValueError:
        return None


def _prior_outcomes(path: str):
    """Replay a dead attempt's flushed ``kind=serve_request`` events:
    returns ``(accounted_rids, lost_rids)`` — rids with a terminal
    outcome in ANY prior attempt, and admitted-to-slot rids with none
    (the in-flight slots the kill took, which THIS attempt classifies
    as lost rather than silently re-serving half-generated work)."""
    import json as json_mod

    from tpudist.serve import resilience as res_lib
    admitted, terminal = set(), set()
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json_mod.loads(line)
                except ValueError:
                    continue          # a torn tail line is not evidence
                if rec.get("kind") != "serve_request":
                    continue
                rid, ev = rec.get("rid"), rec.get("event")
                if rid is None:
                    continue
                if ev == res_lib.ADMITTED:
                    admitted.add(int(rid))
                elif ev in res_lib.TERMINAL_EVENTS:
                    terminal.add(int(rid))
    except OSError:
        return set(), set()
    return terminal, admitted - terminal


class _LoopbackEmitter:
    """MetricsLogger→LiveAggregator fan-out without a socket: the serve
    CLI is single-process, so the coordinator IS the worker and records
    can be ingested directly (same record shapes the TCP bus carries)."""

    def __init__(self, agg):
        self.agg = agg

    def emit(self, rec: Dict[str, Any]) -> None:
        try:
            self.agg.ingest(rec)
        except Exception:
            pass   # telemetry must never take down the serve loop


def run(args: argparse.Namespace) -> Dict[str, Any]:
    import jax

    from tpudist.config import ModelConfig, ParallelConfig, resolve_trace
    from tpudist.metrics import MetricsLogger, log0
    from tpudist.obs import live as live_lib
    from tpudist.obs import trace as trace_lib
    from tpudist.parallel.mesh import build_mesh
    from tpudist.serve import flight as flight_lib
    from tpudist.serve import scheduler as sched
    from tpudist.serve import tune as serve_tune
    from tpudist.serve.engine import PagedServeEngine, init_params

    model_cfg = ModelConfig(
        name=args.model, vocab_size=args.vocab_size,
        n_layers=args.n_layers, d_model=args.d_model,
        n_heads=args.n_heads, n_kv_heads=args.n_kv_heads,
        d_ff=args.d_ff, max_seq_len=args.max_seq,
        n_experts=args.n_experts, expert_top_k=args.expert_top_k,
        head_dim=args.head_dim, sliding_window=args.sliding_window,
        n_experts_held=args.n_experts_held,
        n_shared_experts=args.n_shared_experts,
        # the family's released settings: blocks of 4 (the one length the
        # engine has built), the mask token the vocabulary's last id
        **({"block_length": 4, "mask_token_id": args.vocab_size - 1,
            "rope_theta": 1e6, "norm_eps": 1e-6}
           if args.model == "sdarmoe" else {}),
        # the family's released settings beside the widths the flags give
        **({"q_lora_rank": args.q_lora_rank,
            "kv_lora_rank": args.kv_lora_rank,
            "qk_nope_head_dim": args.qk_nope_head_dim,
            "qk_rope_head_dim": args.qk_rope_head_dim,
            "v_head_dim": args.v_head_dim,
            "n_zero_experts": args.n_zero_experts,
            "d_ff_dense": args.d_ff_dense, "routed_scaling": 6.0,
            "rope_theta": 1e7, "norm_eps": 1e-5}
           if args.model == "longcatflash" else {}))
    mesh = build_mesh(ParallelConfig())
    # same resolver as the train lane (flag > $TPUDIST_TRACE > on for
    # the switch; --trace-dir > $TPUDIST_TRACE_DIR > --save-dir for the
    # destination): serve tracing was previously gated on --trace-dir
    # alone, which made the pod-wide TPUDIST_TRACE=off escape hatch —
    # and default-on flight timelines — silently train-only
    trace_on, trace_dir = resolve_trace(args)
    tracer = trace_lib.configure(enabled=trace_on)

    # --requeue-attempt's PRESENCE (any value, 0 included) means the
    # launcher's supervision loop owns this run: outcome events must
    # reach disk at boundaries, because a preemption may kill us and
    # the NEXT attempt classifies from what survived
    supervised = args.requeue_attempt is not None
    attempt = args.requeue_attempt or 0
    os.makedirs(args.save_dir, exist_ok=True)
    metrics_path = os.path.join(args.save_dir, "metrics.jsonl")
    # a resumed attempt reads the DEAD attempt's flushed outcome events
    # before this attempt appends its own
    prior_done, prior_lost = (set(), set())
    if attempt > 0:
        prior_done, prior_lost = _prior_outcomes(metrics_path)
    metrics = MetricsLogger(path=metrics_path)
    run_id = live_lib.resolve_run_id(jax.process_count())
    metrics.extra["run_id"] = run_id
    metrics.extra["requeue_attempt"] = attempt
    # name the trace artifact like every other artifact of the attempt
    tracer.run_info.update(run_id=run_id, requeue_attempt=attempt)

    # the live bus: the aggregator (alert engine + alerts.jsonl +
    # live_status.json) runs whenever live is ON — $TPUDIST_LIVE=on
    # without a port keeps it exporter-less (the drills' mode); a port
    # additionally serves Prometheus /metrics
    live_on = bool(args.live_port) or os.environ.get(
        "TPUDIST_LIVE", "").lower() in ("on", "1", "true")
    agg = server = None
    if live_on:
        agg = live_lib.LiveAggregator(out_dir=args.save_dir,
                                      run_id=run_id, metrics=None,
                                      stall_timeout_s=0)
        if args.live_port:
            server = live_lib.LiveHttpServer(agg, port=args.live_port)
            log0(f"tpudist: serve live exporter on "
                 f":{server.port}/metrics")
        metrics.emitter = _LoopbackEmitter(agg)

    # the chaos plane's serve surface (tpudist.chaos, --chaos /
    # $TPUDIST_CHAOS): serve_kill / serve_slow fire at decode-dispatch
    # boundaries via the scheduler's hook; request_garbage folds seeded
    # malformed requests into the arrival stream below. Off constructs
    # nothing, same as the train CLI.
    chaos_rt = None
    if args.chaos:
        from tpudist import chaos as chaos_lib
        chaos_rt = chaos_lib.ChaosRuntime(
            chaos_lib.ChaosPlan.parse(args.chaos),
            process_index=jax.process_index(), metrics=metrics)
        log0(f"tpudist: chaos on: {chaos_rt.plan.describe()}")

    from tpudist.serve import resilience as res_lib
    resilience = res_lib.ResilienceConfig(
        queue_cap=max(args.queue_cap, 0),
        ttft_deadline_s=max(args.ttft_deadline_ms, 0.0) / 1e3,
        adapt=args.adapt == "on",
        max_new_cap=max(args.adapt_max_new_cap, 0),
        # malformed-request rejection is on whenever ANY resilience or
        # chaos knob is: the garbage family's contract is an admission
        # rejection, never an engine crash
        validate=bool(args.chaos or args.queue_cap
                      or args.ttft_deadline_ms or args.adapt == "on"))

    params = init_params(model_cfg, mesh, seed=args.seed)

    cand = serve_tune.ServeCandidate(
        decode_k=args.decode_k,
        kv_page_tokens=min(args.kv_page_tokens, args.max_seq),
        speculate_k=max(args.speculate_k, 0))
    if args.serve_tune != "off":
        cache_dir = (args.tune_cache_dir
                     or os.environ.get("TPUDIST_AUTOTUNE_CACHE_DIR")
                     or os.path.join(args.save_dir, "tune"))
        with trace_lib.span("serve_tune", cat="tune",
                            mode=args.serve_tune):
            out = serve_tune.autotune_serve(
                model_cfg, mesh, params, slots=args.slots,
                max_seq=args.max_seq, prompt_pad=args.prompt_pad,
                mode=args.serve_tune, cache_dir=cache_dir, start=cand,
                metrics=metrics)
        cand = out.tuned
        log0(f"tpudist: serve tune {out.status} ({out.source}): "
             f"decode_k={cand.decode_k} "
             f"kv_page_tokens={cand.kv_page_tokens} "
             f"speculate_k={cand.speculate_k} "
             f"[{out.trials} trial(s)]")

    ladder = (res_lib.default_ladder(cand.decode_k)
              if resilience.adapt else None)
    engine = PagedServeEngine(
        model_cfg, mesh, slots=args.slots, max_seq=args.max_seq,
        prompt_pad=args.prompt_pad, decode_k=cand.decode_k,
        page_tokens=cand.kv_page_tokens, pages=max(args.kv_pages, 0),
        speculate_k=cand.speculate_k, adapt_ladder=ladder)
    with trace_lib.span("serve_warmup", cat="serve"):
        engine.warmup(params)

    # program memory (obs.memledger): warmup just compiled every pinned
    # program, so their memory_analysis is readable off the request
    # clock. It also feeds the allocator's memory bound: admission maps
    # only the pages device HBM can afford beside the params and the
    # programs' MEASURED scratch (falling back to the 4x-params
    # heuristic on backends without memory planning — the choice is
    # logged, and a shrunk cap backpressures at admission instead of
    # dying in RESOURCE_EXHAUSTED)
    from tpudist import engine as engine_lib
    from tpudist.obs import memledger as memledger_lib
    program_mem = engine.program_memory()
    params_bytes = engine_lib.state_bytes_per_device(params)
    hbm_bytes = int(engine_lib._device_hbm_bytes())
    temp, temp_complete = memledger_lib.program_temp_bytes(program_mem)
    cap = engine.alloc.set_memory_bound(
        hbm_bytes=hbm_bytes, params_bytes=params_bytes,
        program_temp_bytes=temp if temp_complete else None)
    log0(f"tpudist: serve kv memory bound "
         f"({engine.alloc.bound_source}): {cap}/{engine.spec.pages} "
         f"pages mappable in {hbm_bytes / 2**20:.0f} MB HBM")

    prefix_len = max(args.shared_prefix, 0)
    shared_prefix = (sched.shared_prefix_tokens(
        min(prefix_len, args.prompt_pad), args.vocab_size, args.seed)
        if prefix_len else None)
    requests = sched.make_requests(
        args.requests, prompt_pad=args.prompt_pad,
        vocab_size=args.vocab_size, max_new=args.max_new_tokens,
        rate=args.request_rate, seed=args.seed,
        prefix_len=prefix_len)
    if chaos_rt is not None:
        # request_garbage: the fault IS the malformed requests — fold
        # them into the (deterministic) schedule; admission rejects
        span = max((r.arrival_s for r in requests), default=0.0)
        rid_base = len(requests)
        for ev in chaos_rt.consume_request_garbage():
            garbage = sched.make_garbage_requests(
                chaos_rt.plan, ev, rid_base=rid_base,
                prompt_pad=args.prompt_pad, vocab_size=args.vocab_size,
                span_s=span)
            requests.extend(garbage)
            rid_base += len(garbage)

    n_lost = 0
    if attempt > 0:
        # honest supervision accounting: a prior attempt's in-flight
        # slots are LOST (their KV state died with the engine — a
        # half-generated answer is not resumable), its queued/unserved
        # requests are replayed from the deterministic schedule
        for rid in sorted(prior_lost):
            metrics.log(kind="serve_request", rid=rid,
                        event=res_lib.LOST)
            tracer.instant(res_lib.LOST, cat="serve", rid=rid)
            n_lost += 1
        remaining = [r for r in requests
                     if r.rid not in prior_done
                     and r.rid not in prior_lost]
        shift = min((r.arrival_s for r in remaining), default=0.0)
        requests = [dataclasses.replace(r, arrival_s=r.arrival_s - shift)
                    for r in remaining]
        metrics.log(kind="serve_resume",
                    completed_prior=len(prior_done), lost=n_lost,
                    replayed=len(requests))
        metrics.flush()
        log0(f"tpudist: serve resume (attempt {attempt}): "
             f"{len(prior_done)} done in prior attempt(s), {n_lost} "
             f"in-flight lost, replaying {len(requests)}")

    virtual = None
    if args.virtual_clock:
        virtual = res_lib.VirtualTiming(
            prefill_s=args.virtual_prefill_ms / 1e3,
            decode_s=args.virtual_decode_ms / 1e3)
    summary = sched.run_serve(engine, params, requests, metrics=metrics,
                              resilience=resilience, chaos=chaos_rt,
                              virtual=virtual,
                              flush_events=True if supervised else None,
                              shared_prefix=shared_prefix)
    engine.assert_two_programs()

    summary["run_id"] = run_id
    summary["model"] = args.model
    summary["requeue_attempt"] = attempt
    if attempt > 0:
        # the summary-level ``lost`` is everything THIS attempt knows
        # was lost: in-process losses are impossible (a kill that takes
        # slots never writes a summary), so the resumed attempt's
        # classification of the dead attempt's in-flight slots IS the
        # number — lifted here so the report/bench lanes surface it
        # (the ``partition`` block stays the attempt-local checked
        # ledger, where lost is 0 by construction)
        summary["lost"] = n_lost
        summary["completed_prior"] = len(prior_done)
    cache_bytes = engine.spec.bytes
    summary["kv_cache_bytes"] = cache_bytes
    metrics.log(kind="serve",
                **{k: v for k, v in summary.items()
                   if k not in ("results", "alert_events", "thresholds")})
    metrics.flush()

    # the serve lane's HBM ledger (obs.memledger): params + KV pool
    # (pool pages incl. the trash page + page table + window rings —
    # PagedCacheSpec.bytes) + the pinned
    # programs' scratch, partitioned exactly against device HBM and
    # persisted as <save-dir>/memledger.json for the forensics CLI and
    # the next run's feed-forward margin. Advisory: never fails serve.
    try:
        ledger = memledger_lib.build_ledger(
            total_hbm_bytes=hbm_bytes, params_bytes=params_bytes,
            kv_pool_bytes=cache_bytes, programs=program_mem,
            mode="serve", run_id=run_id)
        metrics.log(kind="memledger",
                    **memledger_lib.ledger_record(ledger))
        metrics.flush()
        memledger_lib._atomic_write(
            os.path.join(args.save_dir, memledger_lib.LEDGER_NAME),
            json.dumps(ledger, indent=1))
        log0(f"tpudist: memledger {ledger['headroom_status']}: "
             f"{100 * ledger['headroom_fraction']:.1f}% headroom of "
             f"{ledger['total_hbm_bytes'] / 2**20:.0f} MB HBM "
             f"(params {params_bytes / 2**20:.1f} MB, kv_pool "
             f"{cache_bytes / 2**20:.2f} MB, temp "
             f"{ledger['buckets']['program_temp'] / 2**20:.1f} MB, "
             f"{'exact' if ledger['exact'] else 'INEXACT'})")
    except Exception as e:
        log0(f"tpudist: memledger skipped ({e!r})")

    log0(f"tpudist: serve {summary['status']}: "
         f"{summary['completed']}/{summary['requests']} requests, "
         f"{summary['generated_tokens']} tokens in "
         f"{summary['wall_s']:.3f}s "
         f"({summary['tokens_per_sec_per_chip']} tok/s/chip), "
         f"ttft p99 {summary['ttft_p99_s']}s, "
         f"itl p99 {summary['itl_p99_s']}s "
         f"[{summary['prefill_compiles']} prefill / "
         f"{summary['decode_compiles']} decode / "
         f"{summary['verify_compiles']} verify compile(s), "
         f"kv cache {cache_bytes / 2**20:.2f} MB, "
         f"{summary['kv_pages_used_peak']}"
         f"/{summary['kv_pages_total']} pages peak, "
         f"spec accept {summary['spec_accept_rate']}]")

    if args.bench_out:
        _write_bench(args.bench_out, args, summary)
        log0(f"tpudist: serve bench -> {args.bench_out}")

    if tracer.enabled:
        # full pod export, like the train lane: trace.worker<i>.json
        # per process plus the merged pod_trace.json on the
        # coordinator — with the serve-specific presentation appended
        # (per-slot request tracks, ph="C" KV occupancy counters)
        pi, pc = jax.process_index(), jax.process_count()
        extra = flight_lib.build_extra_events(
            tracer.events(process_index=pi), process_index=pi)
        tinfo = trace_lib.export_pod_trace(
            trace_dir, process_index=pi, process_count=pc,
            tracer=tracer, extra_events=extra)
        log0(f"tpudist: serve trace -> {tinfo['local_path']} "
             f"({tinfo['spans']} spans, {len(extra)} slot-track/"
             f"counter events"
             + (f", merged {tinfo['merged_path']}"
                if tinfo["merged_path"] else "") + ")")
    if server is not None:
        server.close()
    if agg is not None:
        agg.close()
    metrics.close()
    return summary


def _write_bench(path: str, args: argparse.Namespace,
                 summary: Dict[str, Any]) -> None:
    """``--bench-out``: the run summary as one JSON document — one
    metric headline, per-gate detail, thresholds, the device it ran
    on."""
    import jax
    doc = {
        "metric": "serve_tokens_per_sec_per_chip",
        "value": summary["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip",
        "detail": {k: summary.get(k) for k in (
            "run_id", "model", "requests", "completed",
            "generated_tokens", "truncated", "wall_s", "dispatches",
            "slots", "decode_k", "kv_cache_bytes",
            "tokens_per_sec", "queue_depth_max", "queue_depth_mean",
            "ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s",
            "e2e_p50_s", "e2e_p99_s", "prefill_compiles",
            "decode_compiles", "verify_compiles", "n_chips",
            "arrived", "admitted", "shed_at_admission",
            "expired_in_queue", "rejected", "lost", "completed_prior",
            "shed_fraction", "queue_cap", "ttft_deadline_s",
            "adapt_level", "decode_k_ladder", "requeue_attempt",
            "kv_page_tokens", "kv_pages_total", "kv_pages_used_peak",
            "kv_pages_used_mean", "active_slots_peak", "spec_accept_rate",
            "speculate_k",
            "shared_prefix_len", "kv_window_tokens_total",
            "kv_window_tokens_peak", "moe_pairs_per_expert_mean",
            "moe_experts_hit_mean", "moe_blocks_mean",
            "moe_pairs_zero_mean", "moe_pairs_all_mean",
            "moe_zero_share")},
        "slo": slo_lib.slo_block(summary),
        "device": jax.devices()[0].device_kind,
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)


def main(argv: Optional[Sequence[str]] = None) -> int:
    from tpudist.utils import enable_compilation_cache, tune_tpu
    tune_tpu()
    enable_compilation_cache()
    args = parse_args(argv)
    verdict_path = os.environ.get("TPUDIST_VERDICT_PATH")
    status = slo_lib.FAIL
    try:
        summary = run(args)
        status = summary["status"]
    except Exception as e:
        print(f"tpudist: serve failed: {e!r}", file=sys.stderr,
              flush=True)
    if verdict_path:
        try:
            from tpudist import verdict as verdict_lib
            verdict_lib.write_final_status(verdict_path, status)
        except Exception as e:
            print(f"tpudist: verdict plumbing failed: {e!r}",
                  file=sys.stderr, flush=True)
    # an UNGATEABLE run (nothing measured) is not a latency regression
    return 1 if status == slo_lib.FAIL else 0


if __name__ == "__main__":
    sys.exit(main())
