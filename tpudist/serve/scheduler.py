"""Continuous batching: Poisson arrivals, slot admission, SLO accounting.

The host half of the serving engine. Requests arrive on an open-loop
Poisson schedule (a synthetic stand-in for "millions of users" — rate,
prompt lengths and generation budgets are all seeded, so a serve run is
reproducible end to end), pass ADMISSION CONTROL (bounded queue,
per-request TTFT deadlines, malformed-request rejection —
:mod:`tpudist.serve.resilience`), queue until a slot frees, prefill
into the free slot once the page allocator grants its pages, and
decode continuously: every dispatch is one
compiled superstep over the WHOLE slot batch, with completed slots
freed and refilled between dispatches — no draining, no batch
reshaping, no recompiles.

Under overload the queue does NOT grow unboundedly: arrivals past
``queue_cap`` are shed at admission, and accepted requests that age
past ``ttft_deadline_s`` while still queued are expired before they
ever touch a slot — so the requests the pod DOES serve keep a bounded
TTFT instead of every percentile inheriting the backlog. Every arrival
lands in exactly one ledger bucket (``arrived == admitted +
shed_at_admission + expired_in_queue + rejected``, checked exactly),
and every shed/expiry decision reads ONE monotonic clock sample per
scheduler boundary — no wall-clock reads inside the decision path, so
the seeded schedule sheds the same requests every run (bitwise, under
the drill's virtual clock).

Latency accounting happens here because only the host sees the request
clock: TTFT spans arrival → the fenced prefill that produced the first
token (queue wait included); ITL attributes each token in a decode
dispatch ``dispatch_wall / decode_k`` (see :mod:`tpudist.serve.slo`).
The loop feeds every observation to an :class:`~tpudist.obs.alerts.
AlertEngine` over the shared rules table, so an SLO breach FIRES as an
alert mid-run — same numbers, same thresholds as the exit verdict.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from tpudist import rules as rules_lib
from tpudist.obs import trace as trace_lib
from tpudist.obs.alerts import AlertEngine
from tpudist.serve import resilience as res_lib
from tpudist.serve import slo as slo_lib
from tpudist.serve.engine import PagedServeEngine


@dataclasses.dataclass(frozen=True)
class Request:
    """One synthetic inference request."""

    rid: int
    arrival_s: float          # offset from run start
    tokens: np.ndarray        # (prompt_pad,) int32, padded prompt
    prompt_len: int
    max_new: int


def shared_prefix_tokens(prefix_len: int, vocab_size: int,
                         seed: int) -> np.ndarray:
    """The run's shared system-prompt prefix: ``prefix_len`` seeded
    tokens every ``--shared-prefix`` request starts with. One function,
    used by both the request generator and the engine's prefix
    registration, so the two can never disagree about the bytes."""
    rng = np.random.default_rng([int(seed), 17])
    return rng.integers(0, vocab_size,
                        size=(int(prefix_len),)).astype(np.int32)


def make_requests(n: int, *, prompt_pad: int, vocab_size: int,
                  max_new: int, rate: float, seed: int,
                  prompt_min: int = 0,
                  prefix_len: int = 0) -> List[Request]:
    """Seeded synthetic request stream.

    Arrivals: Poisson process at ``rate`` requests/s (exponential
    inter-arrival gaps); ``rate <= 0`` means every request is present at
    t=0 — the closed-loop mode benchmarks and probes use. Prompts reuse
    the training data's deterministic next-token structure (the affine
    map of data.make_synthetic_tokens) with per-request lengths drawn
    from [prompt_min, prompt_pad]. ``prefix_len > 0`` gives every
    request the same :func:`shared_prefix_tokens` system-prompt prefix
    (per-request tails stay distinct; prompt lengths never undercut the
    prefix) — the engine's shared-prefix workload. ``prefix_len
    = 0`` is bit-for-bit the original stream (identical rng draws)."""
    rng = np.random.default_rng(seed)
    if rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    else:
        arrivals = np.zeros(n)
    prompt_min = min(max(1, prompt_min or prompt_pad // 2), prompt_pad)
    if prefix_len > 0:
        prefix_len = min(int(prefix_len), prompt_pad)
        prompt_min = max(prompt_min, prefix_len)
    lens = rng.integers(prompt_min, prompt_pad + 1, size=n)
    first = rng.integers(0, vocab_size, size=(n, 1)).astype(np.int32)
    toks = np.empty((n, prompt_pad), np.int32)
    toks[:, :1] = first
    for t in range(1, prompt_pad):
        toks[:, t] = (toks[:, t - 1] * 7 + 3) % vocab_size
    if prefix_len > 0:
        # overwrite the head with the shared prefix; the tail keeps
        # each request's own chain (seeded from its own first token)
        toks[:, :prefix_len] = shared_prefix_tokens(
            prefix_len, vocab_size, seed)[None, :]
    out = []
    for i in range(n):
        padded = toks[i].copy()
        padded[lens[i]:] = 0     # pad-token tail, masked by prompt_len
        out.append(Request(rid=i, arrival_s=float(arrivals[i]),
                           tokens=padded, prompt_len=int(lens[i]),
                           max_new=int(max_new)))
    return out


def ngram_draft(history, k: int) -> List[int]:
    """The cheap host-side draft proposer for speculative decoding:
    repeat what followed the last occurrence of the current token in
    the sequence so far (classic n-gram lookup, n=1), falling back to
    repeating the token itself. Deterministic and model-free — the
    verify forward accepts exactly the prefix of the draft that matches
    the target's own greedy choices, so a bad draft costs nothing but
    its acceptance rate."""
    h = [int(t) for t in history]
    out: List[int] = []
    for _ in range(int(k)):
        last = h[-1]
        nxt = last
        for i in range(len(h) - 2, -1, -1):
            if h[i] == last:
                nxt = h[i + 1]
                break
        out.append(nxt)
        h.append(nxt)
    return out


def validate_request(req: Request, *, prompt_pad: int,
                     vocab_size: int) -> Optional[str]:
    """Admission-time request validation: the reason a malformed
    request is rejected, or None for a well-formed one. The engine's
    compiled prefill assumes a (prompt_pad,) int32 prompt with an
    in-range true length and a positive budget — anything else must be
    turned away HERE (the ``request_garbage`` chaos family's contract:
    garbage costs itself a rejection, never the engine)."""
    pl, mn = req.prompt_len, req.max_new
    if not isinstance(pl, (int, np.integer)) or not (0 < pl <= prompt_pad):
        return "bad_prompt_len"
    if not isinstance(mn, (int, np.integer)) or mn < 1:
        return "bad_max_new"
    try:
        toks = np.asarray(req.tokens)
    except Exception:
        return "bad_tokens"
    if toks.shape != (prompt_pad,):
        return "bad_shape"
    if not np.issubdtype(toks.dtype, np.integer):
        return "bad_dtype"
    if ((toks[:pl] < 0) | (toks[:pl] >= vocab_size)).any():
        return "bad_token"
    return None


def make_garbage_requests(plan, event, *, rid_base: int, prompt_pad: int,
                          vocab_size: int, span_s: float
                          ) -> List[Request]:
    """The ``request_garbage`` chaos family's payload: ``n`` seeded
    malformed requests spread over the arrival window, each broken a
    deterministically-chosen way (out-of-range tokens, zero/oversized
    prompt_len, dead budget, wrong shape, float tokens). Derived from
    the plan's keyed byte stream, so the same spec injects the same
    garbage every run and the fuzz drill is replayable."""
    from tpudist.chaos import plan as plan_mod
    n = int(event.args.get("n", 4))
    raw = plan_mod.garbage_bytes(plan, event, n=8 * max(n, 1))
    modes = ("bad_token", "zero_len", "over_len", "bad_max_new",
             "bad_shape", "bad_dtype")
    out: List[Request] = []
    for i in range(n):
        chunk = raw[8 * i:8 * i + 8]
        arrival = (int.from_bytes(chunk[:4], "big") / 0xFFFFFFFF) \
            * max(span_s, 0.0)
        mode = modes[chunk[4] % len(modes)]
        tokens = np.zeros((prompt_pad,), np.int32)
        prompt_len, max_new = max(1, prompt_pad // 2), 4
        if mode == "bad_token":
            tokens[0] = vocab_size + 1 + chunk[5]
        elif mode == "zero_len":
            prompt_len = 0
        elif mode == "over_len":
            prompt_len = prompt_pad + 1 + chunk[5] % 8
        elif mode == "bad_max_new":
            max_new = -int(chunk[5])
        elif mode == "bad_shape":
            tokens = np.zeros((prompt_pad + 3,), np.int32)
        elif mode == "bad_dtype":
            tokens = np.zeros((prompt_pad,), np.float64) + 0.5
        out.append(Request(rid=rid_base + i, arrival_s=float(arrival),
                           tokens=tokens, prompt_len=prompt_len,
                           max_new=max_new))
    return out


@dataclasses.dataclass
class _Slot:
    req: Request
    generated: int
    first_token_s: Optional[float]   # None: no token has come back yet
    output: List[int]
    budget: int               # max_new after any adapt-time truncation
    # a model that generates by blocks: the denoising step at which each
    # emitted token was unmasked, and the (position in block, token, step)
    # of what the last block computed past the budget and did not emit
    steps: List[int] = dataclasses.field(default_factory=list)
    surplus: List[tuple] = dataclasses.field(default_factory=list)


def run_serve(engine: PagedServeEngine, params, requests: List[Request], *,
              metrics: Any = None, tick_every: int = 8,
              clock: Callable[[], float] = time.perf_counter,
              n_chips: Optional[int] = None,
              resilience: Optional[res_lib.ResilienceConfig] = None,
              chaos: Any = None,
              virtual: Optional[res_lib.VirtualTiming] = None,
              flush_events: Optional[bool] = None,
              shared_prefix: Optional[np.ndarray] = None
              ) -> Dict[str, Any]:
    """Drive the engine over the request stream; returns the run summary
    (percentiles, throughput, per-gate SLO statuses, the exact shed
    partition, compile counts).

    The engine must already be warmed
    (:meth:`PagedServeEngine.warmup`) so the request clock never pays
    XLA compilation. ``metrics`` (a MetricsLogger) receives periodic
    ``kind=serve_tick`` records plus per-request
    ``kind=serve_request`` outcome events; the caller logs the final
    ``kind=serve`` summary so it can stamp its own fields in.

    ``resilience`` turns on admission control / degradation
    (:class:`~tpudist.serve.resilience.ResilienceConfig`; None keeps
    the pre-resilience open-loop behavior bit-for-bit). ``chaos`` is a
    :class:`~tpudist.chaos.inject.ChaosRuntime` whose serve surface
    (``on_serve_dispatch``) fires at every decode-dispatch boundary.
    ``virtual`` switches the request clock to deterministic virtual
    time (:class:`~tpudist.serve.resilience.VirtualTiming`) — the
    overload drill's bitwise mode. ``flush_events`` arms BOUNDARY
    flushes of the buffered per-request outcome events — before every
    chaos dispatch hook and on the tick cadence — so a kill cannot eat
    the evidence the resumed attempt classifies from (default: on when
    chaos or resilience is armed; the CLI also arms it under the
    launcher's requeue supervision).

    The KV pool shapes admission and dispatch, never the accounting:
    slot admission asks the page allocator (a pool too full leaves the
    request WAITING — backpressure, not shedding — while a request too
    big to EVER fit this pool is ``rejected`` with reason
    ``kv_pages_exhausted``, in the same exact ledger partition); each
    dispatch first grows every live slot's page mapping to cover the
    positions it will write (growth failure evicts, freeing the pages); finishing a slot
    returns its pages. ``shared_prefix`` (token array) registers a
    refcounted shared system-prompt prefix once, served from the same
    pages to every admission that starts with it. With
    ``engine.speculate_k >= 2`` decode dispatches become draft+verify:
    the host :func:`ngram_draft` proposes, the engine's ONE batched
    verify forward accepts — greedy output stays bitwise identical to
    plain decode, only the tokens-per-dispatch changes. Speculation
    runs at adapt level 0 only (the degradation ladder's rungs are
    plain decode programs).

    A model that generates by diffusion over blocks (``engine.block``)
    changes three things here. Its prefill yields NO token: the request is
    admitted (``admitted`` carries the wait up to the fenced prefill, as
    ever) and its time to first token is taken when its first block comes
    back, arrival -> that dispatch's fence, logged as a
    ``kind=serve_first_tokens`` record. A dispatch hands back up to
    ``block`` tokens a slot, fewer for a request's first block (the
    prompt's remainder opens it) and for its last (the budget is honoured
    to the token: the surplus is computed and not emitted), so the
    per-token latency of a dispatch is its wall over the slot's own
    count, as under speculation. And each result carries, beside its
    tokens, the denoising step at which each was unmasked
    (``unmask_step``) and what its last block computed past the budget
    (``surplus``): what a reference needs to replay the denoising."""
    import jax
    if n_chips is None:
        n_chips = max(jax.device_count(), 1)
    res = resilience or res_lib.ResilienceConfig()
    if virtual is not None:
        clock = virtual.clock
    if flush_events is None:
        flush_events = chaos is not None or res.enabled
    tracer = trace_lib.get()
    stats = slo_lib.LatencyStats()
    alerts = AlertEngine()
    led = res_lib.ShedLedger()
    controller = None
    if res.adapt and len(engine.ladder) > 1:
        controller = res_lib.PressureController(
            res, max_level=len(engine.ladder) - 1)
    cur_level = 0
    cur_k = engine.ladder[0]
    pending = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
    waiting: deque = deque()         # accepted, not yet slotted
    slots: List[Optional[_Slot]] = [None] * engine.slots
    state = engine.init_state()
    alloc = engine.new_allocator()
    prefix_len = 0
    prefix_arr: Optional[np.ndarray] = None
    if shared_prefix is not None and len(shared_prefix) > 0:
        prefix_arr = np.asarray(shared_prefix, np.int32)
        prefix_len = int(min(len(prefix_arr), engine.prompt_pad))
        prefix_arr = prefix_arr[:prefix_len]
        # fills the prefix's full pages via the ONE prefill program;
        # a pool that cannot hold the prefix is a config error, raised
        state = engine.register_prefix(params, state, prefix_arr,
                                       prefix_len)
    spec_k = engine.speculate_k
    block = engine.block
    # a model whose programs count what they did (pairs routed to the
    # experts held here): read back after the fence the loop makes anyway
    # and carried as arguments of the ``prefill`` and ``decode_step``
    # spans, with how full each kind of cache state is
    counts_on = engine.counted
    # a block model's, summed over dispatches: forwards a slot, commits
    # fused into a next block's first step, forwards the programs launched
    forwards = commits = launched = 0
    window_peak = 0
    moe_sum = {"moe_pairs_per_expert": 0.0, "moe_experts_hit": 0.0,
               "moe_blocks": 0.0}
    # a model with identity experts counts their pairs beside all pairs
    # routed (``engine.read_stats``: the model's ``EXTRA_STATS``)
    moe_sum.update(dict.fromkeys(
        getattr(engine.model, "EXTRA_STATS", ()), 0.0))
    results: Dict[int, Dict[str, Any]] = {}
    generated = truncated = dispatches = 0
    drafted = accepted = 0          # speculative-draft acceptance
    active_peak = pages_peak = pages_sum = 0
    queue_depths: List[int] = []
    recent_tok: deque = deque(maxlen=max(res.window, 1))
    t0 = clock()

    def is_shared(req: Request) -> bool:
        # a request rides the shared prefix iff its prompt literally
        # starts with it — byte-checked, never assumed
        return (prefix_arr is not None
                and req.prompt_len >= prefix_len
                and np.array_equal(np.asarray(req.tokens)[:prefix_len],
                                   prefix_arr))

    def now() -> float:
        return clock() - t0

    def event(rid: int, ev: str, **kw: Any) -> None:
        # the per-request outcome stream the drill verifier (and a
        # resumed attempt's lost-slot classification) replays. Buffered
        # here — durability comes from the BOUNDARY flushes below (per
        # dispatch ahead of the chaos hook, per tick otherwise), not a
        # write+flush per outcome on the serving host path.
        # Every outcome is ALSO a lifecycle instant on the flight
        # timeline (cat=serve, keyed by rid, same spellings as the
        # resilience vocabulary) — the flight ledger cross-checks the
        # two streams, so they are emitted from the same call site
        tracer.instant(ev, cat="serve", rid=rid, **kw)
        if metrics is None:
            return
        metrics.log(kind="serve_request", rid=rid, event=ev,
                    t_s=round(now(), 6), **kw)

    def shared_refs() -> int:
        # refcounts currently held on the shared-prefix pages
        # (includes the registry's own keep-cached hold)
        if not alloc.shared_pages:
            return 0
        return int(sum(int(alloc.refcount[p])
                       for p in alloc.shared_pages))

    def finish(i: int, why: str) -> None:
        nonlocal truncated
        s = slots[i]
        t_done = now()     # ONE sample: results/stats/event agree
        results[s.req.rid] = {
            "tokens": list(s.output), "prompt_len": s.req.prompt_len,
            "generated": s.generated, "why": why,
            "adapt_truncated": s.budget < s.req.max_new,
            "e2e_s": t_done - s.req.arrival_s}
        if block:
            results[s.req.rid].update(unmask_step=list(s.steps),
                                      surplus=list(s.surplus))
        stats.note_e2e(t_done - s.req.arrival_s)
        if why == "evicted":
            truncated += 1
            led.evicted += 1
        else:
            led.completed += 1
        event(s.req.rid, res_lib.DONE if why == "done" else
              res_lib.EVICTED, slot=i, generated=s.generated,
              e2e_s=round(t_done - s.req.arrival_s, 6),
              decode_s=round(0.0 if s.first_token_s is None
                             else t_done - s.first_token_s, 6),
              # how many of ``generated`` came of the prefill itself
              prefill_tokens=0 if block else 1)
        slots[i] = None
        # pages return to the pool (shared prefix pages drop one
        # refcount; the registry hold keeps them cached). Safe: the
        # device slot is frozen (budget/capacity) or masked out of
        # every future dispatch until its next prefill
        alloc.free_slot(i)

    def expire(t: float) -> None:
        # the accepted queue's head is always the oldest (FIFO in
        # arrival order), so deadline expiry only ever pops from there
        while waiting and t - waiting[0].arrival_s \
                > res.ttft_deadline_s:
            r = waiting.popleft()
            led.expired_queue += 1
            event(r.rid, res_lib.EXPIRED,
                  waited_s=round(t - r.arrival_s, 6))

    def pump(t: float) -> None:
        """Admission control at ONE sampled time ``t``: first expire
        the deadline-aged queue heads, THEN process arrivals against
        the post-expiry queue — a fresh arrival must never be shed at
        the cap by requests that are already dead at the same sampled
        instant. An arrival whose own deadline passed while it sat in
        the schedule backlog counts expired, not shed (it was never
        servable). No clock reads in here: determinism under the
        seeded schedule is exactly this function never asking twice."""
        if res.ttft_deadline_s > 0:
            expire(t)
        while pending and pending[0].arrival_s <= t:
            req = pending.popleft()
            led.arrived += 1
            # the flight chain's opening marker: every arrived rid gets
            # exactly one, whatever admission then decides
            tracer.instant("arrive", cat="serve", rid=req.rid,
                           arrival_s=round(req.arrival_s, 6),
                           prompt_len=req.prompt_len)
            why = validate_request(
                req, prompt_pad=engine.prompt_pad,
                vocab_size=engine.model_cfg.vocab_size) \
                if res.validate else None
            if why is not None:
                led.rejected += 1
                event(req.rid, res_lib.REJECTED, reason=why)
            elif res.ttft_deadline_s > 0 \
                    and t - req.arrival_s > res.ttft_deadline_s:
                led.expired_queue += 1
                event(req.rid, res_lib.EXPIRED,
                      waited_s=round(t - req.arrival_s, 6))
            elif res.queue_cap and len(waiting) >= res.queue_cap:
                led.shed_admission += 1
                event(req.rid, res_lib.SHED,
                      queue_depth=len(waiting))
            else:
                waiting.append(req)

    def admit() -> None:
        nonlocal generated, state
        t = now()
        pump(t)
        for i in range(engine.slots):
            if slots[i] is not None or not waiting:
                continue
            # peek-then-pop: a denied admission must leave the
            # request at the queue head, not shed it
            req = waiting[0]
            shared = is_shared(req)
            if not alloc.can_ever_admit(req.prompt_len, shared):
                # structurally unservable at this pool size: even
                # an empty pool could not hold the prompt. Reject
                # (exact-partition bucket) instead of wedging the
                # queue head forever
                waiting.popleft()
                led.rejected += 1
                event(req.rid, res_lib.REJECTED,
                      reason="kv_pages_exhausted")
                continue
            pt = engine.spec.page_tokens
            need = -(-req.prompt_len // pt)
            reused = min(need, len(alloc.shared_pages)) \
                if shared else 0
            if not alloc.admit(i, req.prompt_len, shared=shared):
                # pool full RIGHT NOW: backpressure, not shedding —
                # running slots will finish and free pages
                tracer.instant("kv_backpressure", cat="serve",
                               rid=req.rid, slot=i, pages=need)
                break
            tracer.instant("kv_admit", cat="serve", rid=req.rid,
                           slot=i, pages=need,
                           pages_granted=need - reused,
                           shared_pages_reused=reused)
            waiting.popleft()
            budget = req.max_new
            if cur_level > 0 and res.max_new_cap:
                budget = min(budget, res.max_new_cap)
            with tracer.span("admit", cat="serve", rid=req.rid, slot=i):
                pass   # the admission decision itself is host-trivial
            with tracer.span("prefill", cat="serve", rid=req.rid,
                             slot=i, prompt_len=req.prompt_len) as sp:
                with tracer.span("prefill_enqueue", cat="serve"):
                    state, first = engine.prefill(
                        params, state, req.tokens[None, :],
                        req.prompt_len, i, budget,
                        shared_len=alloc.admit_shared_len(shared))
                with tracer.span("prefill_fence", cat="serve"):
                    first = int(first)       # fence: the token exists NOW
                if counts_on and tracer.enabled:
                    sp.note(**engine.read_stats(state))
                if block and tracer.enabled:
                    sp.note(blocks_written=first)
            if virtual is not None:
                virtual.clock.advance(virtual.prefill_s)
            t_first = now()
            led.admitted += 1
            # waited_s is the TTFT; its exact decomposition rides along
            # (queue wait up to the sampled admission instant ``t``,
            # then prefill+fence up to ``t_first``) so the flight
            # ledger can assert ttft == queue_wait + prefill without
            # any extra clock reads on the decision path
            event(req.rid, res_lib.ADMITTED, slot=i,
                  waited_s=round(t_first - req.arrival_s, 6),
                  queue_wait_s=round(t - req.arrival_s, 6),
                  prefill_s=round(t_first - t, 6))
            if block:
                # no token yet: the first come with the first block
                slots[i] = _Slot(req=req, generated=0, first_token_s=None,
                                 output=[], budget=budget)
            else:
                stats.note_ttft(t_first - req.arrival_s)
                generated += 1
                slots[i] = _Slot(req=req, generated=1,
                                 first_token_s=t_first, output=[first],
                                 budget=budget)
            spent = budget <= 1 and not block   # the prefill's token was it
            if spent or req.prompt_len >= engine.max_seq:
                finish(i, "done" if spent else "evicted")
            t = now()
            pump(t)        # arrivals that landed during the prefill

    def observe_slos(summ: Dict[str, Any]) -> None:
        alerts.observe("ttft", summ["ttft_p99_s"])
        alerts.observe("itl", summ["itl_p99_s"])
        alerts.observe("serve_shed", led.shed_fraction())
        wall = now()
        if wall > 0 and generated:
            alerts.observe("tokens_per_chip",
                           generated / wall / n_chips)

    def fenced(toks, valid):
        """The dispatch's tokens on the host: the wait for the device."""
        with tracer.span("decode_fence", cat="serve"):
            return np.asarray(toks), np.asarray(valid)

    while len(results) + led.shed_total() < len(requests):
        with tracer.span("admit_pass", cat="serve"):
            admit()
        occupied = [i for i in range(engine.slots) if slots[i] is not None]
        if not occupied:
            if waiting:
                # accepted work and free slots, but every slot FINISHED
                # inside this admit pass (an instant budget<=1 / full-
                # prompt completion): loop straight back into admit.
                # This check must come BEFORE the next-arrival wait —
                # warping the clock past queued servable requests would
                # expire (or TTFT-inflate) them with slots sitting free
                continue
            # nothing running and nothing queued: wait out the gap to
            # the next scheduled arrival (bounded — the generator's
            # schedule is finite)
            if pending:
                if virtual is not None:
                    virtual.clock.wait_until(t0 + pending[0].arrival_s)
                else:
                    with tracer.span("idle_wait", cat="serve"):
                        time.sleep(min(0.002, max(
                            0.0, pending[0].arrival_s - now())))
                continue
            break
        # depth sampled once per DISPATCH (not per idle busy-wait pass:
        # a sparse schedule would drown the mean in idle-gap zeros and
        # grow the sample list unboundedly)
        queue_depths.append(len(waiting))
        # speculation only at full service: the degradation ladder's
        # rungs are plain decode programs, and a downshifted pod wants
        # its smallest dispatch, not a wider verify window
        spec_on = spec_k >= 2 and cur_level == 0
        # grow each live slot's mapping to cover every position
        # this dispatch can write; a slot the pool cannot grow for
        # is evicted (truncated output, pages fund the others)
        width = spec_k if spec_on else cur_k
        for i in occupied:
            s = slots[i]
            if block:
                # the whole block the dispatch writes, ahead of what the
                # slot holds: blocks are aligned to absolute positions
                last = (s.req.prompt_len + s.generated) // block * block \
                    + block - 1
            else:
                last = s.req.prompt_len + s.generated + width - 2
            last = min(last, engine.max_seq - 1)
            if not alloc.ensure(i, last):
                finish(i, "evicted")
        occupied = [i for i in range(engine.slots)
                    if slots[i] is not None]
        if not occupied:
            continue
        # the chaos serve surface: serve_kill dies HERE (a dispatch
        # boundary — the compiled program is never torn mid-flight),
        # serve_slow returns the stall it injected so virtual time can
        # account it. Flush the buffered outcome events FIRST: a kill
        # at this boundary must not eat the evidence the resumed
        # attempt's lost-slot classification replays.
        stall_s = 0.0
        if chaos is not None:
            if flush_events and metrics is not None:
                metrics.flush()
            stall_s = float(chaos.on_serve_dispatch(dispatches) or 0.0)
        t_dispatch = clock()
        occ_mask = np.array([s is not None for s in slots])
        # the pages the slots' rows map: what a page-bounded decode read
        # copies a layer a token step (the masked read stages the layer's
        # whole pool whatever they hold)
        pages_now = alloc.pages_used()
        if spec_on:
            draft = np.zeros((engine.slots, spec_k - 1), np.int32)
            for i in occupied:
                s = slots[i]
                draft[i] = ngram_draft(
                    list(s.req.tokens[:s.req.prompt_len]) + s.output,
                    spec_k - 1)
            with tracer.span("verify_step", cat="serve",
                             active=len(occupied), window=spec_k,
                             kv_full_pages=pages_now):
                with tracer.span("decode_enqueue", cat="serve"):
                    state, toks, valid, _emitted = engine.verify(
                        params, state, draft, dispatch_active=occ_mask)
                toks, valid = fenced(toks, valid)
        else:
            with tracer.span("decode_step", cat="serve",
                             active=len(occupied), decode_k=cur_k,
                             kv_full_pages=pages_now) as sp:
                with tracer.span("decode_enqueue", cat="serve"):
                    state, toks, valid = engine.decode(
                        params, state, cur_k, dispatch_active=occ_mask)
                toks, valid = fenced(toks, valid)
                if counts_on:
                    counted = engine.read_stats(state)
                    for name in moe_sum:
                        moe_sum[name] += counted[name]
                    if tracer.enabled:
                        sp.note(**counted)
                        if engine.windowed:
                            sp.note(kv_window_tokens=alloc
                                    .window_tokens_used())
                if block:
                    unmasked_at = engine.read_block(state)
                    # the block's denoising forwards a slot, and one for
                    # each commit of a previous block that rode with them
                    ran = block * len(occupied) + counted["commits_fused"]
                    forwards += ran
                    commits += counted["commits_fused"]
                    launched += counted["forwards_launched"]
                    if tracer.enabled:
                        sp.note(forwards=ran, blocks=len(occupied),
                                tokens_emitted=int(valid.sum()))
        if virtual is not None:
            dt = virtual.decode_s + stall_s
            virtual.clock.advance(dt)
        else:
            dt = (clock() - t_dispatch) + stall_s
        emit = tracer.begin("emit", cat="serve")
        dispatches += 1
        active_peak = max(active_peak, len(occupied))
        pages_peak = max(pages_peak, pages_now)
        pages_sum += pages_now
        window_peak = max(window_peak, alloc.window_tokens_used())
        if tracer.enabled:
            # KV-pool occupancy sample, one per dispatch: becomes
            # the ph="C" counter track in pod_trace.json so cache
            # pressure sits on the same timeline as the request
            # spans causing it. Guarded: the refcount walk (and
            # the clock read inside instant) must cost nothing
            # when tracing is off
            tracer.instant("kv_pages", cat="serve_counter",
                           used=alloc.pages_used(),
                           total=engine.spec.pages,
                           shared_refs=shared_refs())
        varies = spec_on or bool(block)
        t_back = now() if block else None    # ONE sample a dispatch
        if varies:
            # a verify dispatch emits a VARIABLE token count per slot:
            # ITL attributes the dispatch wall over each slot's own
            # accepted run (that is speculation's whole win); so does a
            # block dispatch (a request's first and last blocks are cut)
            tot_new = int(valid.sum())
            mean_new = tot_new / max(len(occupied), 1)
            recent_tok.append(dt / mean_new if mean_new > 0 else dt)
            per_tok = None
        else:
            per_tok = dt / cur_k
            recent_tok.append(per_tok)
        for i in occupied:
            col_valid = valid[:, i]
            n_new = int(col_valid.sum())
            if n_new:
                slots[i].output.extend(
                    int(t) for t in toks[col_valid, i])
                slots[i].generated += n_new
                generated += n_new
                stats.note_itl(dt / n_new if varies else per_tok, n_new)
                if spec_on:
                    accepted += n_new - 1    # minus the bonus token
                    drafted += spec_k - 1
            s = slots[i]
            if block:
                at = unmasked_at[:, i]
                s.steps.extend(int(a) for a in at[col_valid])
                s.surplus = [(int(w), int(toks[w, i]), int(at[w]))
                             for w in np.flatnonzero(~col_valid & (at >= 0))]
                if n_new and s.first_token_s is None:
                    # time to first token: arrival -> the return of the
                    # request's first block
                    s.first_token_s = t_back
                    ttft = s.first_token_s - s.req.arrival_s
                    stats.note_ttft(ttft)
                    tracer.instant("first_tokens", cat="serve",
                                   rid=s.req.rid, slot=i, tokens=n_new)
                    if metrics is not None:
                        metrics.log(kind="serve_first_tokens",
                                    rid=s.req.rid, tokens=n_new,
                                    t_s=round(s.first_token_s, 6),
                                    ttft_s=round(ttft, 6))
            # per-slot decode attribution on the flight timeline: the
            # ledger sums these per rid and pins the total against the
            # terminal event's generated count (first token excluded)
            if spec_on:
                tracer.instant("decode_emit", cat="serve",
                               rid=s.req.rid, slot=i, tokens=n_new,
                               dispatch=dispatches,
                               drafted=spec_k - 1,
                               accepted=max(n_new - 1, 0))
            else:
                tracer.instant("decode_emit", cat="serve",
                               rid=s.req.rid, slot=i, tokens=n_new,
                               dispatch=dispatches)
            if s.generated >= s.budget:
                finish(i, "done")
            elif s.req.prompt_len + s.generated > engine.max_seq:
                # aligned with the DEVICE freeze (lengths >= max_seq,
                # i.e. prompt + generated - 1 tokens cached): the slot
                # is evicted exactly when its page filled, so truncated
                # output length does not depend on decode_k and a freed
                # slot is never still device-active
                finish(i, "evicted")
        tracer.end(emit)
        # SLO grading on the tick cadence, not per dispatch: summary()
        # sorts every accumulated sample, and that host work would land
        # in the inter-dispatch gap — inflating the very ITL it grades
        if dispatches % max(tick_every, 1) != 0:
            continue
        tick = tracer.begin("tick", cat="serve")
        if flush_events and metrics is not None:
            # amortised durability for the supervised-but-unchaosed
            # path (a REAL preemption can land anywhere): at most one
            # tick window of outcome events is at risk, not the run
            metrics.flush()
        summ = stats.summary()
        observe_slos(summ)
        if controller is not None:
            recent_itl = (sum(recent_tok) / len(recent_tok)
                          if recent_tok else None)
            trans = controller.observe(len(waiting), recent_itl)
            if trans is not None:
                frm, to, reason = trans
                cur_level = to
                cur_k = engine.ladder[min(to, len(engine.ladder) - 1)]
                if metrics is not None:
                    # every ladder move is a flushed, auditable record:
                    # the drill verifier and the live view both read it
                    metrics.log(kind="serve_adapt",
                                t_s=round(now(), 4), from_level=frm,
                                to_level=to, decode_k=cur_k,
                                queue_depth=len(waiting),
                                reason=reason)
                    metrics.flush()
        if metrics is not None:
            wall = now()
            metrics.log(kind="serve_tick", t_s=round(wall, 4),
                        queue_depth=len(waiting),
                        active_slots=sum(s is not None for s in slots),
                        completed=len(results),
                        generated_tokens=generated,
                        shed_total=led.shed_total(),
                        shed_fraction=led.shed_fraction(),
                        adapt_level=cur_level,
                        decode_k=cur_k,
                        ttft_p99_s=summ["ttft_p99_s"],
                        itl_p99_s=summ["itl_p99_s"],
                        tokens_per_sec_per_chip=(
                            round(generated / wall / n_chips, 3)
                            if wall > 0 else None),
                        # self-describing fixed-bucket histograms: the
                        # live Prometheus exporter renders native
                        # _bucket{le=...} series straight from these —
                        # raw samples never leave the serving host
                        ttft_hist=stats.ttft_hist(),
                        itl_hist=stats.itl_hist(),
                        # the pool's footprint: what is actually
                        # allocated (pool + table + rings)
                        kv_pages_used=alloc.pages_used(),
                        kv_pages_total=engine.spec.pages,
                        kv_cache_bytes=engine.spec.bytes,
                        kv_shared_refs=shared_refs(),
                        spec_accept_rate=(round(accepted / drafted, 4)
                                          if drafted else None))
        tracer.end(tick)

    wall_s = now()
    # an empty run measured NOTHING: throughput is None (→ the gate
    # grades UNGATEABLE, the three-valued contract every tpudist gate
    # follows), not a 0.0 that would read as an SLO fail
    tps = (generated / wall_s) if generated and wall_s > 0 else None
    tps_chip = tps / n_chips if tps is not None else None
    summ = stats.summary()
    if requests:
        observe_slos(summ)   # runs shorter than a tick still fire
    grade = slo_lib.grade(summ["ttft_p99_s"], summ["itl_p99_s"],
                          tps_chip, shed_fraction=led.shed_fraction())
    return {
        "requests": len(requests), "completed": len(results),
        "generated_tokens": generated, "truncated": truncated,
        "wall_s": round(wall_s, 4), "dispatches": dispatches,
        "slots": engine.slots, "decode_k": engine.decode_k,
        "tokens_per_sec": round(tps, 3) if tps is not None else None,
        "tokens_per_sec_per_chip": (round(tps_chip, 3)
                                    if tps_chip is not None else None),
        "n_chips": n_chips,
        "queue_depth_max": max(queue_depths, default=0),
        "queue_depth_mean": (round(float(np.mean(queue_depths)), 3)
                             if queue_depths else 0.0),
        # the exact shed partition (headline fields lifted for the
        # bench/report consumers; the full checked block under
        # "partition")
        "arrived": led.arrived, "admitted": led.admitted,
        "shed_at_admission": led.shed_admission,
        "expired_in_queue": led.expired_queue,
        "rejected": led.rejected, "lost": led.lost,
        "shed_total": led.shed_total(),
        "shed_fraction": led.shed_fraction(),
        "partition": led.as_dict(),
        "queue_cap": res.queue_cap,
        "ttft_deadline_s": res.ttft_deadline_s,
        "adapt_level": cur_level, "decode_k_current": cur_k,
        "decode_k_ladder": list(engine.ladder),
        "adapt_transitions": (list(controller.transitions)
                              if controller is not None else []),
        **{k: (round(v, 6) if v is not None else None)
           for k, v in summ.items()},
        **grade,
        "alert_events": alerts.events,
        "prefill_compiles": engine.compile_counts()[0],
        "decode_compiles": engine.compile_counts()[1],
        "verify_compiles": len(engine.verify_traces),
        "active_slots_peak": active_peak,
        "kv_page_tokens": engine.spec.page_tokens,
        "kv_pages_total": engine.spec.pages,
        "kv_pages_used_peak": pages_peak,
        # mapped pages a dispatch, mean: what the decode read owes a layer
        # a token step, of kv_pages_total
        "kv_pages_used_mean": (round(pages_sum / dispatches, 2)
                               if dispatches else None),
        # the other kind of state (a model with window layers): tokens
        # held in the per-slot rings, of slots x ring_tokens
        "kv_window_tokens_total": (engine.spec.slots
                                   * engine.spec.ring_tokens),
        "kv_window_tokens_peak": window_peak,
        # means over the decode dispatches (None: the model counts none)
        **{name + "_mean": (round(v / dispatches, 4)
                            if counts_on and dispatches else None)
           for name, v in moe_sum.items()},
        # of all pairs routed, the share on identity experts: routed work
        # done without a matrix (None: the model has no such experts)
        "moe_zero_share": (round(moe_sum["moe_pairs_zero"]
                                 / moe_sum["moe_pairs_all"], 4)
                           if moe_sum.get("moe_pairs_all") else None),
        "spec_accept_rate": (round(accepted / drafted, 4)
                             if drafted else None),
        "speculate_k": spec_k,
        # a model that generates by blocks (None elsewhere): forwards run
        # a token emitted (a slot's block, and its commit in the slot's
        # next dispatch: about block + 1 over block on full blocks), the
        # commits that rode so and the forwards the programs launched, and
        # tokens a dispatch handed back, over all slots
        "block_length": block,
        "forwards_per_token": (round(forwards / generated, 4)
                               if block and generated else None),
        "commits_fused": commits if block else None,
        "forwards_launched": launched if block else None,
        "tokens_per_dispatch": (round(generated / dispatches, 2)
                                if block and dispatches else None),
        "shared_prefix_len": prefix_len,
        "ttft_hist": stats.ttft_hist(),
        "itl_hist": stats.itl_hist(),
        "results": results,
        "thresholds": {rule: rules_lib.resolve(rule)
                       for rule, _ in slo_lib.SERVE_RULES},
    }
