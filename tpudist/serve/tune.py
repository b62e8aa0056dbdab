"""Measured-probe autotuner for the serving engine's knobs.

The PR-4 train tuner generalised: the decode superstep length
(``decode_k`` — tokens per dispatch per slot), the KV pool's page
length (:mod:`tpudist.serve.kvcache`) and the speculation window all
move decode throughput, and the right answer depends on the model
shape, mesh and device kind — exactly the situation the train tuner
replaced static heuristics with measurement for. This module reuses that
machinery wholesale: the same persisted fingerprint-keyed JSON cache
(:mod:`tpudist.tune.cache`, ``prefix="serve"`` so the two knob schemas
never collide in one file), the same deterministic walk discipline
(ordered-axis ascent with plateau preference and regress early-stop,
:mod:`tpudist.tune.search` constants), and the same contract: the
search NEVER commits a point that measures slower than the heuristic
start, a second run of the same (model, topology, serve shape) costs
zero probe trials, and a probing failure degrades to the heuristics,
never to a dead run.

The probe is closed-loop decode throughput: build the candidate's
engine, prefill every slot, then time whole decode supersteps with all
slots active — tokens/s at full occupancy, the number the
``tokens_per_chip`` SLO gate grades.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from tpudist import verdict as verdict_lib
from tpudist.tune import cache as cache_mod
from tpudist.tune import search as search_mod

# Decode-k ladder: geometric like the train tuner's k axis — the curve's
# knee is what matters, not every integer. Capped where per-dispatch
# latency starts to dominate ITL attribution (slo: ITL = wall / k).
DECODE_K_LADDER = (1, 2, 4, 8, 16, 32)
# Page sizes worth probing and verify window widths (window includes the
# pending last token, so 2 is the smallest real speculation).
KV_PAGE_TOKENS_LADDER = (8, 16, 32)
SPECULATE_K_LADDER = (2, 4, 8)

DEFAULT_PROBE_DISPATCHES = 8
DEFAULT_PROBE_REPEATS = 3
DEFAULT_TRIALS = 8


@dataclasses.dataclass(frozen=True)
class ServeCandidate:
    """One point in the serve knob space. ``kv_page_tokens`` is the
    pool's page length (the engine's default); ``speculate_k = 0`` is
    plain decode, >= 2 the draft+verify window."""

    decode_k: int = 8
    kv_page_tokens: int = 8
    speculate_k: int = 0

    def replace(self, **kw) -> "ServeCandidate":
        return dataclasses.replace(self, **kw)

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def validate_serve_tuned(tuned: Dict[str, Any]) -> bool:
    """Knob sanity for a cached serve record (the ``validate`` hook of
    :func:`tpudist.tune.cache.load`): an insane decode_k, or a record
    of another knob schema (no paged knobs yet, or a storage ``layout``
    still) is a cache MISS (re-probe), never a crash in the engine."""
    if set(tuned) != {"decode_k", "kv_page_tokens", "speculate_k"}:
        return False              # another schema: re-probe
    if int(tuned["decode_k"]) < 1:
        return False
    pt, sk = int(tuned["kv_page_tokens"]), int(tuned["speculate_k"])
    return pt >= 1 and (sk == 0 or sk >= 2)


def fingerprint(model_cfg, mesh, *, slots: int, max_seq: int,
                prompt_pad: int,
                device_kind: Optional[str] = None) -> str:
    """Fingerprint of the serve tuning situation — everything that moves
    the decode-throughput curve: model shape, cache geometry, mesh,
    device kind/counts, software versions. Same recipe as the train
    tuner's (tune.cache.fingerprint); distinct payload because the knob
    space is distinct."""
    import hashlib
    import json

    import jax

    from tpudist.version import __version__
    if device_kind is None:
        try:
            device_kind = jax.devices()[0].device_kind
        except Exception:
            device_kind = "unknown"
    payload = {
        "schema": cache_mod.SCHEMA,
        "what": "serve",
        # knob-space generation: bumped when the candidate schema grows
        # (paged knobs joined at 2) so records from an older walk never
        # alias a fingerprint whose search space they never saw
        "knobs": 2,
        "model": dataclasses.asdict(model_cfg),
        "slots": int(slots),
        "max_seq": int(max_seq),
        "prompt_pad": int(prompt_pad),
        "mesh": dict(zip(mesh.axis_names,
                         (int(s) for s in mesh.devices.shape))),
        "n_devices": jax.device_count(),
        "n_processes": jax.process_count(),
        "device_kind": device_kind,
        "jax": jax.__version__,
        "tpudist": __version__,
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class ServeProbeResult:
    """One candidate's measured decode-throughput trial."""

    tokens_per_sec: float
    dispatch_ms: float
    feasible: bool = True
    error: Optional[str] = None
    spread: float = 0.0      # (max-min)/min over repeats: noise floor
    tokens: int = 0          # tokens actually generated per timed run


def probe_candidate(model_cfg, mesh, params, cand: ServeCandidate, *,
                    slots: int, max_seq: int, prompt_pad: int,
                    n_dispatches: int = DEFAULT_PROBE_DISPATCHES,
                    repeats: int = DEFAULT_PROBE_REPEATS
                    ) -> ServeProbeResult:
    """Measure one candidate: build its engine, prefill every slot, time
    ``repeats`` runs of ``n_dispatches`` decode supersteps at full
    occupancy. Estimator over repeats is the MIN elapsed (one-sided host
    noise, same reasoning as tune.probe). The pool is the default
    full-capacity one (the probe measures the program, not an
    artificial page famine); a speculative candidate times
    draft+verify dispatches and counts the tokens the verifies actually
    emitted — fenced ``lengths`` deltas, not ``k × dispatches``, since
    acceptance is workload-dependent and crediting rejected drafts
    would let speculation look free. Never raises — any failure (OOM,
    a page longer than the sequence) is a pruned ``feasible=False``
    result."""
    import jax
    import numpy as np

    from tpudist.serve.engine import PagedServeEngine
    try:
        spec_k = cand.speculate_k
        engine = PagedServeEngine(
            model_cfg, mesh, slots=slots, max_seq=max_seq,
            prompt_pad=prompt_pad, decode_k=cand.decode_k,
            page_tokens=cand.kv_page_tokens, speculate_k=spec_k)
        # per-slot decode budget must cover every timed dispatch so the
        # whole probe runs at full occupancy (an emptying batch would
        # flatter small decode_k); shrink the dispatch count if the
        # cache pages cannot hold that many tokens
        width = spec_k if spec_k >= 2 else cand.decode_k
        room = (max_seq - prompt_pad - 1) // width
        n_disp = max(1, min(int(n_dispatches), room))
        budget = n_disp * width + 2
        prompt = np.arange(prompt_pad, dtype=np.int32) \
            % model_cfg.vocab_size

        def fill() -> Any:
            state = engine.init_state()
            engine.new_allocator()
            outs = []
            for s in range(slots):
                engine.alloc.admit(s, prompt_pad)  # full-capacity
                # pool: cannot fail at probe shapes
                state, first = engine.prefill(
                    params, state, prompt[None, :], prompt_pad, s,
                    budget)
                outs.append([int(x) for x in prompt] + [int(first)])
            # map every page up front: the probe times dispatch
            # compute, not incremental host allocation
            for s in range(slots):
                engine.alloc.ensure(s, max_seq - 1)
            return state, outs

        def dispatch(state, outs):
            if spec_k >= 2:
                from tpudist.serve.scheduler import ngram_draft
                draft = np.zeros((slots, spec_k - 1), np.int32)
                for s in range(slots):
                    draft[s] = ngram_draft(outs[s], spec_k - 1)
                state, toks, valid, _ = engine.verify(params, state,
                                                      draft)
                tv, vv = np.asarray(toks), np.asarray(valid)  # fence —
                # the NEXT draft needs these tokens; part of the cost
                for s in range(slots):
                    outs[s].extend(int(x) for x in tv[vv[:, s], s])
                return state, toks
            state, toks, _ = engine.decode(params, state)
            return state, toks

        # warm: compile every program off the timed path
        state, outs = fill()
        state, toks = dispatch(state, outs)
        np.asarray(toks)
        times: List[float] = []
        tokens = 0
        for _ in range(repeats):
            state, outs = fill()
            len0 = int(np.asarray(state.lengths).sum())  # fence too
            t0 = time.perf_counter()
            toks = None
            for _ in range(n_disp):
                state, toks = dispatch(state, outs)
            np.asarray(toks)                 # fence on the tokens
            times.append(time.perf_counter() - t0)
            # honest token count from the device's own ledger: every
            # emitted token advanced a slot's length by exactly one, a
            # frozen slot's by zero — so an oversized decode_k or a
            # rejected draft can never inflate the estimate
            tokens = int(np.asarray(state.lengths).sum()) - len0
        best = min(times)
        spread = (max(times) - best) / best if best > 0 else 0.0
        return ServeProbeResult(
            tokens_per_sec=tokens / best if best > 0 else 0.0,
            dispatch_ms=best * 1000.0 / n_disp, spread=spread,
            tokens=tokens)
    except Exception as e:
        return ServeProbeResult(
            0.0, float("inf"), feasible=False,
            error=f"{type(e).__name__}: {str(e)[:200]}")


@dataclasses.dataclass(frozen=True)
class ServeTuneOutcome:
    """What the serve tuner decided and how it got there."""

    tuned: ServeCandidate
    source: str                   # cache | probe | heuristic
    status: str                   # verdict SUCCESS/FAIL/UNGATEABLE
    trials: int
    pruned: int
    fingerprint: str
    cache_dir: str
    tokens_per_sec: Optional[float] = None
    baseline_tokens_per_sec: Optional[float] = None


def _search(measure, start: ServeCandidate, *, max_decode_k: int,
            trial_budget: int,
            max_page_tokens: int = 0) -> Dict[str, Any]:
    """Deterministic axis walk sharing the train search's discipline:
    decode_k first (ordered ascent, regress early-stop,
    plateau-prefers-smallest within PLATEAU_TOL — shorter supersteps
    mean honester ITL at indistinguishable throughput), then the paged
    axes at the committed decode_k: ``kv_page_tokens`` (a real win over
    the committed point switches page size; a tie keeps it) and, at the
    committed page size, ``speculate_k`` (same real-win bar:
    acceptance-rate-dependent speedups must MEASURE, never be
    assumed). The committed point NEVER
    measures slower than the start."""
    memo: Dict[ServeCandidate, ServeProbeResult] = {}
    out = {"best": start, "best_tps": 0.0, "baseline_tps": 0.0,
           "trials": 0, "pruned": 0}

    def run(cand: ServeCandidate) -> Optional[ServeProbeResult]:
        if cand in memo:
            return memo[cand]
        if out["trials"] >= trial_budget:
            return None
        res = measure(cand)
        out["trials"] += 1
        if not res.feasible:
            out["pruned"] += 1
        memo[cand] = res
        return res

    base = run(start)
    if base is not None and base.feasible:
        out["baseline_tps"] = out["best_tps"] = base.tokens_per_sec

    ladder = [k for k in DECODE_K_LADDER if k <= max_decode_k]
    if start.decode_k not in ladder:
        ladder = sorted(set(ladder) | {start.decode_k})
    measured = [(start.decode_k, out["best_tps"])] \
        if out["best_tps"] > 0 else []
    prev: Optional[float] = None   # previous LADDER point, scan order —
    # comparing each k against the (possibly mid-ladder) start would
    # false-trigger the regress stop on the very first rung
    for k in ladder:
        if k == start.decode_k:
            prev = out["best_tps"] or prev
            continue
        res = run(start.replace(decode_k=k))
        if res is None:
            break
        if not res.feasible:
            break                # bigger pages cannot refit HBM
        measured.append((k, res.tokens_per_sec))
        if prev is not None and res.tokens_per_sec \
                < prev * (1 - search_mod.REGRESS_STOP):
            break                # past the plateau, curve turned down
        prev = res.tokens_per_sec
    if measured:
        axis_best = max(t for _, t in measured)
        for k, tps in sorted(measured):
            if tps >= axis_best * (1 - search_mod.PLATEAU_TOL):
                out["best"] = out["best"].replace(decode_k=k)
                out["best_tps"] = tps
                break

    # ---- paged axes (serve-mode coordinates, PR 16) ----
    if max_page_tokens > 0:
        for pt in KV_PAGE_TOKENS_LADDER:
            if pt > max_page_tokens \
                    or pt == out["best"].kv_page_tokens:
                continue
            # page size probes without speculation: one axis at a time
            res = run(out["best"].replace(kv_page_tokens=pt,
                                          speculate_k=0))
            if res is None or not res.feasible:
                continue
            if res.tokens_per_sec > out["best_tps"] * (
                    1 + search_mod.PLATEAU_TOL):
                out["best"] = out["best"].replace(kv_page_tokens=pt,
                                                  speculate_k=0)
                out["best_tps"] = res.tokens_per_sec
        for sk in SPECULATE_K_LADDER:
            if sk == out["best"].speculate_k:
                continue
            res = run(out["best"].replace(speculate_k=sk))
            if res is None or not res.feasible:
                continue
            if res.tokens_per_sec > out["best_tps"] * (
                    1 + search_mod.PLATEAU_TOL):
                out["best"] = out["best"].replace(speculate_k=sk)
                out["best_tps"] = res.tokens_per_sec

    # the hard floor: never commit a point slower than the measured start
    if out["best"] != start and out["best_tps"] < out["baseline_tps"]:
        out["best"], out["best_tps"] = start, out["baseline_tps"]
    return out


def autotune_serve(model_cfg, mesh, params, *, slots: int, max_seq: int,
                   prompt_pad: int, mode: str, cache_dir: str,
                   start: Optional[ServeCandidate] = None,
                   trials: int = DEFAULT_TRIALS,
                   n_dispatches: int = DEFAULT_PROBE_DISPATCHES,
                   repeats: int = DEFAULT_PROBE_REPEATS,
                   metrics: Any = None) -> ServeTuneOutcome:
    """Resolve the serve operating point per ``mode`` (``off`` |
    ``probe`` | ``cache-only``), exactly like tune.autotune: cache hit →
    zero trials; miss under ``probe`` → measured search + persist; miss
    under ``cache-only`` (or a probing failure) → the heuristic start,
    honestly labeled. Single-process by design — the serve loop is one
    host driving one mesh (multi-host serving would broadcast the commit
    exactly as tune._sync_candidate does)."""
    start = start or ServeCandidate()
    fp = fingerprint(model_cfg, mesh, slots=slots, max_seq=max_seq,
                     prompt_pad=prompt_pad)
    if mode == "off":
        return _log(ServeTuneOutcome(
            tuned=start, source="heuristic",
            status=verdict_lib.tuning_status("off"), trials=0, pruned=0,
            fingerprint=fp, cache_dir=cache_dir), metrics)

    rec = cache_mod.load(cache_dir, fp, prefix="serve",
                         validate=validate_serve_tuned)
    if rec is not None:
        t = rec["tuned"]
        tuned = ServeCandidate(decode_k=int(t["decode_k"]),
                               kv_page_tokens=int(t["kv_page_tokens"]),
                               speculate_k=int(t["speculate_k"]))
        if tuned.decode_k <= max_seq - prompt_pad:
            return _log(ServeTuneOutcome(
                tuned=tuned, source="cache",
                status=verdict_lib.tuning_status(mode, source="cache"),
                trials=0, pruned=0, fingerprint=fp, cache_dir=cache_dir,
                tokens_per_sec=rec.get("tokens_per_sec"),
                baseline_tokens_per_sec=rec.get(
                    "baseline_tokens_per_sec")), metrics)

    if mode != "probe":
        return _log(ServeTuneOutcome(
            tuned=start, source="heuristic",
            status=verdict_lib.tuning_status(mode, source="heuristic"),
            trials=0, pruned=0, fingerprint=fp, cache_dir=cache_dir),
            metrics)

    def measure(cand: ServeCandidate) -> ServeProbeResult:
        return probe_candidate(model_cfg, mesh, params, cand,
                               slots=slots, max_seq=max_seq,
                               prompt_pad=prompt_pad,
                               n_dispatches=n_dispatches,
                               repeats=repeats)

    try:
        out = _search(measure, start,
                      max_decode_k=max(1, max_seq - prompt_pad - 1),
                      trial_budget=trials,
                      max_page_tokens=max_seq)
    except Exception as e:
        from tpudist.metrics import log0
        log0(f"tpudist: serve autotune probing failed ({e!r}); "
             f"falling back to heuristics")
        return _log(ServeTuneOutcome(
            tuned=start, source="heuristic",
            status=verdict_lib.tuning_status(mode, source="heuristic"),
            trials=0, pruned=0, fingerprint=fp, cache_dir=cache_dir),
            metrics)

    status = verdict_lib.tuning_status(
        mode, source="probe", tuned_steps_per_sec=out["best_tps"],
        baseline_steps_per_sec=out["baseline_tps"])
    cache_mod.store(cache_dir, fp, {
        "tuned": out["best"].as_dict(),
        "tokens_per_sec": out["best_tps"],
        "baseline_tokens_per_sec": out["baseline_tps"],
        "trials": out["trials"], "pruned": out["pruned"],
    }, prefix="serve")
    return _log(ServeTuneOutcome(
        tuned=out["best"], source="probe", status=status,
        trials=out["trials"], pruned=out["pruned"], fingerprint=fp,
        cache_dir=cache_dir, tokens_per_sec=out["best_tps"],
        baseline_tokens_per_sec=out["baseline_tps"]), metrics)


def _log(out: ServeTuneOutcome, metrics: Any) -> ServeTuneOutcome:
    """One ``kind=serve_tune`` record per tuning decision."""
    if metrics is not None:
        metrics.log(kind="serve_tune", status=out.status,
                    source=out.source, trials=out.trials,
                    pruned=out.pruned, fingerprint=out.fingerprint,
                    decode_k=out.tuned.decode_k,
                    kv_page_tokens=out.tuned.kv_page_tokens,
                    speculate_k=out.tuned.speculate_k,
                    tokens_per_sec=out.tokens_per_sec,
                    baseline_tokens_per_sec=out.baseline_tokens_per_sec)
    return out
