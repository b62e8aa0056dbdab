"""Per-request flight ledger: reconstruct and EXACTLY verify serve
timelines from the artifacts a run leaves behind.

The serve lane records three views of every request:

  * lifecycle instants/spans on the ring tracer (``cat=serve``, keyed
    by ``rid``): ``arrive -> kv_admit -> admit -> prefill ->
    decode_emit ... -> {done|evicted|...}``;
  * the flushed ``kind=serve_request`` outcome stream (the same
    spellings — :mod:`tpudist.serve.resilience` owns the vocabulary);
  * the ShedLedger's exact partition in the ``kind=serve`` summary.

This module is the auditor that folds them back together. For every
arrived ``rid`` it reconstructs ONE flight and asserts the chain
grammar exactly: exactly one admission-stage event
(``admitted | shed_admission | expired_queue | rejected``); a
non-admitted verdict IS terminal (no further events); an admitted
flight ends in exactly one outcome (``done | evicted | lost``). The
admitted event's TTFT must equal its own decomposition
(``waited_s == queue_wait_s + prefill_s`` within the pinned
``flight_decomp`` rules-table tolerance), and the aggregate chain
counts must reconcile BITWISE with the ShedLedger partition — the two
accountings derive from the same scheduler but through different code
paths, so a drift here is a real bookkeeping bug, never noise. When a
trace document is supplied (and its ring dropped nothing) the ledger
additionally pins the span view against the event view: one prefill
span per admitted rid, and the per-rid sum of ``decode_emit`` tokens
equal to the terminal event's ``generated`` count minus the prefill
token (``prefill_tokens``: none where the model generates by blocks).

Also home to the pod-trace presentation helpers: the per-slot track
copies and the ph="C" KV-pool occupancy counter events the serve CLI
appends to ``pod_trace.json`` via ``export_pod_trace(extra_events=)``.

Stdlib-only by design (same contract as :mod:`tpudist.serve.slo`): the
report CLI folds the "Request flights" section, and the
``python -m tpudist.serve.flight`` verifier exits 0/1, with jax
uninstalled.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from tpudist import rules as rules_lib
from tpudist.serve import resilience as res_lib
from tpudist.serve import slo as slo_lib

SERVE_CAT = "serve"             # lifecycle spans/instants, keyed by rid
COUNTER_CAT = "serve_counter"   # KV-pool occupancy samples

# Per-slot Perfetto tracks: slot i's copies land on tid BASE+i — far
# above the tracer's small per-thread tid enumeration, so the slot rows
# sort below the host threads and never collide with them.
SLOT_TID_BASE = 1000

# Flight-stage instant names that are NOT serve_request outcomes (the
# outcome spellings come from the resilience vocabulary).
ARRIVE = "arrive"

_COUNT_KEYS = ("arrived", "admitted", "shed_at_admission",
               "expired_in_queue", "rejected", "completed", "evicted",
               "lost")

_ADMISSION_TO_KEY = {
    res_lib.ADMITTED: "admitted",
    res_lib.SHED: "shed_at_admission",
    res_lib.EXPIRED: "expired_in_queue",
    res_lib.REJECTED: "rejected",
}

_OUTCOME_TO_KEY = {
    res_lib.DONE: "completed",
    res_lib.EVICTED: "evicted",
    res_lib.LOST: "lost",
}


# --------------------------------------------------- pod-trace presentation

def slot_track_events(events: List[Dict[str, Any]], *,
                      process_index: int = 0) -> List[Dict[str, Any]]:
    """Per-slot track copies of the serve lifecycle events.

    Every ``cat=serve`` event whose args carry a ``slot`` is duplicated
    onto tid ``SLOT_TID_BASE + slot`` (with a ``thread_name`` metadata
    row naming the track ``slot<i>``), so Perfetto shows one row per
    serving slot with that slot's admissions, prefills, decode
    emissions and terminals in arrival order. Copies are tagged
    ``args.track = "slot"`` so the ledger's span accounting can skip
    them (they are presentation, not new evidence)."""
    out: List[Dict[str, Any]] = []
    slots = set()
    for e in events:
        if e.get("cat") != SERVE_CAT:
            continue
        args = e.get("args") or {}
        slot = args.get("slot")
        if slot is None or args.get("track"):
            continue
        ev = dict(e)
        ev["pid"] = process_index
        ev["tid"] = SLOT_TID_BASE + int(slot)
        ev["args"] = dict(args, track="slot")
        out.append(ev)
        slots.add(int(slot))
    meta = [{"ph": "M", "name": "thread_name", "pid": process_index,
             "tid": SLOT_TID_BASE + s, "args": {"name": f"slot{s}"}}
            for s in sorted(slots)]
    return meta + out


def kv_counter_events(events: List[Dict[str, Any]], *,
                      process_index: int = 0) -> List[Dict[str, Any]]:
    """ph="C" Chrome counter events from the scheduler's ``kv_pages``
    occupancy samples (``cat=serve_counter`` instants, one per decode
    dispatch). Emitted as a stacked used/free pair (the stack height IS
    the pool size) plus a separate shared-prefix refcount series, on
    the same timestamps as the request spans."""
    out: List[Dict[str, Any]] = []
    for e in events:
        if e.get("cat") != COUNTER_CAT or e.get("name") != "kv_pages":
            continue
        a = e.get("args") or {}
        used = int(a.get("used") or 0)
        total = int(a.get("total") or 0)
        base = {"cat": COUNTER_CAT, "ph": "C", "ts": e.get("ts", 0.0),
                "pid": process_index, "tid": 0}
        out.append(dict(base, name="kv_pages",
                        args={"used": used,
                              "free": max(total - used, 0)}))
        out.append(dict(base, name="kv_shared_refs",
                        args={"refs": int(a.get("shared_refs") or 0)}))
    return out


def build_extra_events(events: List[Dict[str, Any]], *,
                       process_index: int = 0) -> List[Dict[str, Any]]:
    """Everything the serve CLI appends to its worker trace doc before
    the pod merge: per-slot request tracks + KV occupancy counters."""
    return (slot_track_events(events, process_index=process_index)
            + kv_counter_events(events, process_index=process_index))


# -------------------------------------------------------- reconstruction

def reconstruct(records: List[Dict[str, Any]],
                trace_doc: Optional[Dict[str, Any]] = None
                ) -> Dict[int, Dict[str, Any]]:
    """Fold the ``kind=serve_request`` stream (and optionally a trace
    document) into one flight dict per rid. File order is preserved per
    rid — the scheduler emits events in lifecycle order, so order IS
    the chain."""
    flights: Dict[int, Dict[str, Any]] = {}
    for rec in records:
        if rec.get("kind") != "serve_request" or rec.get("rid") is None:
            continue
        rid = int(rec["rid"])
        f = flights.setdefault(rid, {"rid": rid, "events": []})
        f["events"].append({k: v for k, v in rec.items()
                            if k != "kind"})
    if trace_doc is not None:
        _attach_trace(flights, trace_doc)
    return flights


def _attach_trace(flights: Dict[int, Dict[str, Any]],
                  trace_doc: Dict[str, Any]) -> None:
    """Per-rid span accounting from a (worker or merged pod) trace doc.

    Only host 0's original thread events count as evidence: the merge
    re-pids every worker, per-slot track copies are tagged, and on a
    multi-process run every process records the same SPMD scheduler —
    counting more than one view would double every span."""
    meta = trace_doc.get("metadata") or {}
    dropped = int(meta.get("dropped") or 0)
    for e in trace_doc.get("traceEvents", []):
        if e.get("ph") != "X" or e.get("cat") != SERVE_CAT:
            continue
        if e.get("pid") not in (0, None):
            continue
        args = e.get("args") or {}
        if args.get("track"):
            continue
        rid = args.get("rid")
        if rid is None:
            continue
        rid = int(rid)
        f = flights.setdefault(rid, {"rid": rid, "events": [],
                                     "trace_only": True})
        spans = f.setdefault("spans", {})
        name = e.get("name")
        spans[name] = spans.get(name, 0) + 1
        if name == "decode_emit":
            f["decode_tokens"] = (f.get("decode_tokens", 0)
                                  + int(args.get("tokens") or 0))
    for f in flights.values():
        if "spans" in f:
            f["trace_dropped"] = dropped


# ------------------------------------------------------------ verification

def verify(flights: Dict[int, Dict[str, Any]],
           partition: Optional[Dict[str, Any]] = None, *,
           tol: Optional[float] = None) -> Dict[str, Any]:
    """The exactness pass. Returns a summary dict whose ``exact`` field
    is True iff every chain parsed, every decomposition met the
    tolerance, every trace cross-check held, and (when given) the
    chain-count partition reconciled bitwise with the ShedLedger."""
    if tol is None:
        tol = rules_lib.resolve("flight_decomp")
    problems: List[str] = []
    counts = {k: 0 for k in _COUNT_KEYS}
    worst = 0.0
    decomposed = 0
    trace_checked = 0
    for rid in sorted(flights):
        f = flights[rid]
        evs = [e.get("event") for e in f["events"]]
        if not evs:
            problems.append(f"rid {rid}: trace spans but no "
                            f"serve_request events")
            continue
        counts["arrived"] += 1
        unknown = [e for e in evs if e not in _ADMISSION_TO_KEY
                   and e not in _OUTCOME_TO_KEY]
        if unknown:
            problems.append(f"rid {rid}: unknown events {unknown}")
        adm = [e for e in evs if e in _ADMISSION_TO_KEY]
        outs = [e for e in evs if e in _OUTCOME_TO_KEY]
        if len(adm) != 1:
            problems.append(f"rid {rid}: {len(adm)} admission-stage "
                            f"events {adm} (want exactly 1)")
            continue
        counts[_ADMISSION_TO_KEY[adm[0]]] += 1
        if adm[0] != res_lib.ADMITTED:
            # a non-admitted verdict IS the terminal state
            if len(evs) != 1:
                problems.append(f"rid {rid}: events after terminal "
                                f"admission verdict {adm[0]}: {evs}")
            continue
        if len(outs) != 1:
            problems.append(f"rid {rid}: {len(outs)} outcome events "
                            f"{outs} after admission (want exactly 1)")
            continue
        counts[_OUTCOME_TO_KEY[outs[0]]] += 1
        if evs.index(adm[0]) > evs.index(outs[0]):
            problems.append(f"rid {rid}: outcome {outs[0]} precedes "
                            f"admission")
        adm_ev = f["events"][evs.index(res_lib.ADMITTED)]
        err = _decomp_error(adm_ev)
        if err is not None:
            decomposed += 1
            worst = max(worst, err)
            if err > tol:
                problems.append(
                    f"rid {rid}: ttft decomposition off by {err:.2e} s "
                    f"(waited_s={adm_ev.get('waited_s')} vs "
                    f"queue_wait_s+prefill_s, tol {tol:.2e})")
        tp = _trace_problems(rid, f, outs[0])
        if tp is not None:
            trace_checked += 1
            problems.extend(tp)
    if partition is not None:
        for k in _COUNT_KEYS:
            want = partition.get(k)
            if want is None or int(want) == counts[k]:
                continue
            problems.append(f"partition mismatch: {k} reconstructed "
                            f"{counts[k]} != ledger {int(want)}")
    return {
        "flights": len(flights),
        "counts": counts,
        "exact": not problems,
        "problems": problems,
        "decomposed": decomposed,
        "ttft_decomp_worst_s": round(worst, 9),
        "ttft_decomp_tol_s": tol,
        "ttft_decomp_status": (slo_lib.FAIL if rules_lib.breached(
            "flight_decomp", worst, tol) else slo_lib.SUCCESS),
        "partition_checked": partition is not None,
        "trace_checked": trace_checked,
    }


def _decomp_error(adm_ev: Dict[str, Any]) -> Optional[float]:
    """|ttft - (queue_wait + prefill)| when the ADMITTED event carries
    the decomposition; None on pre-flight-tracing artifacts."""
    waited = adm_ev.get("waited_s")
    q = adm_ev.get("queue_wait_s")
    p = adm_ev.get("prefill_s")
    if waited is None or q is None or p is None:
        return None
    return abs(float(waited) - (float(q) + float(p)))


def _trace_problems(rid: int, f: Dict[str, Any],
                    outcome: str) -> Optional[List[str]]:
    """Span-vs-event cross-checks for one ADMITTED flight; None when no
    trace evidence was attached or the ring dropped spans (an overrun
    ring under-counts exactly the oldest flights — skipping is honest,
    silently passing would not be)."""
    spans = f.get("spans")
    if spans is None or f.get("trace_dropped", 0) > 0:
        return None
    out: List[str] = []
    n_pre = spans.get("prefill", 0)
    if n_pre != 1:
        out.append(f"rid {rid}: {n_pre} prefill spans in trace "
                   f"(want exactly 1)")
    if outcome in (res_lib.DONE, res_lib.EVICTED):
        term = [e for e in f["events"] if e.get("event") == outcome]
        gen = term[-1].get("generated")
        got = f.get("decode_tokens", 0)
        # what the prefill itself produced: its one token, or none where
        # the model generates by blocks (older artifacts do not say: 1)
        pre = int(term[-1].get("prefill_tokens", 1))
        if gen is not None and got != int(gen) - pre:
            out.append(f"rid {rid}: decode_emit tokens {got} != "
                       f"generated-{pre} ({int(gen) - pre})")
    return out


# ------------------------------------------------------------- aggregates

def decomposition(flights: Dict[int, Dict[str, Any]]
                  ) -> Dict[str, Dict[str, Any]]:
    """p50/p99 of each TTFT/e2e component across the reconstructed
    flights (nearest-rank, same percentile the SLO grader uses)."""
    comps: Dict[str, List[float]] = {
        "ttft": [], "queue_wait": [], "prefill": [], "decode": [],
        "e2e": []}
    for f in flights.values():
        for e in f["events"]:
            ev = e.get("event")
            if ev == res_lib.ADMITTED:
                for key, field in (("ttft", "waited_s"),
                                   ("queue_wait", "queue_wait_s"),
                                   ("prefill", "prefill_s")):
                    if e.get(field) is not None:
                        comps[key].append(float(e[field]))
            elif ev in (res_lib.DONE, res_lib.EVICTED):
                for key, field in (("decode", "decode_s"),
                                   ("e2e", "e2e_s")):
                    if e.get(field) is not None:
                        comps[key].append(float(e[field]))
    out: Dict[str, Dict[str, Any]] = {}
    for key, vals in comps.items():
        p50 = slo_lib.percentile(vals, 50)
        p99 = slo_lib.percentile(vals, 99)
        out[key] = {"n": len(vals),
                    "p50_s": round(p50, 6) if p50 is not None else None,
                    "p99_s": round(p99, 6) if p99 is not None else None}
    return out


def shed_timeline(flights: Dict[int, Dict[str, Any]], *,
                  limit: int = 100) -> List[Dict[str, Any]]:
    """The non-completion terminals in time order (when sheds, expiries
    and evictions clustered tells the capacity story): up to ``limit``
    of them, each ``{t_s, rid, event}``."""
    rows: List[Dict[str, Any]] = []
    for f in flights.values():
        for e in f["events"]:
            ev = e.get("event")
            if ev in (res_lib.SHED, res_lib.EXPIRED, res_lib.REJECTED,
                      res_lib.EVICTED, res_lib.LOST):
                rows.append({"t_s": e.get("t_s"), "rid": f["rid"],
                             "event": ev})
    rows.sort(key=lambda r: (r["t_s"] is None, r["t_s"], r["rid"]))
    return rows[:limit]


# --------------------------------------------------------------- loading

def load_metrics(path: str) -> List[Dict[str, Any]]:
    """metrics.jsonl as a record list; malformed lines are skipped (a
    crash mid-write leaves at most one torn tail line)."""
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def load_trace(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def find_partition(records: List[Dict[str, Any]]
                   ) -> Tuple[Optional[Dict[str, Any]], int]:
    """(partition, requeue_attempt) from the last ``kind=serve``
    summary record. Bitwise reconciliation is only sound on attempt 0:
    a resumed attempt's ledger partitions only ITS OWN arrivals while
    the replayed event stream spans every attempt."""
    part: Optional[Dict[str, Any]] = None
    attempt = 0
    for rec in records:
        if rec.get("kind") == "serve" and rec.get("partition"):
            part = rec["partition"]
            attempt = int(rec.get("requeue_attempt") or 0)
    return part, attempt


# -------------------------------------------------------------------- CLI

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tpudist.serve.flight",
        description="Reconstruct and exactly verify per-request serve "
                    "flights from a run directory (jax-free).")
    ap.add_argument("--run-dir", default=None,
                    help="directory holding metrics.jsonl (+ optional "
                         "pod_trace.json / trace.worker0.json)")
    ap.add_argument("--metrics", default=None,
                    help="explicit metrics.jsonl path")
    ap.add_argument("--trace", default=None,
                    help="explicit trace json path")
    args = ap.parse_args(argv)
    metrics_path = args.metrics or (
        os.path.join(args.run_dir, "metrics.jsonl") if args.run_dir
        else None)
    if not metrics_path or not os.path.exists(metrics_path):
        print("flight: no metrics.jsonl "
              f"({metrics_path or '--run-dir/--metrics required'})",
              file=sys.stderr)
        return 2
    records = load_metrics(metrics_path)
    trace_doc = None
    trace_path = args.trace
    if trace_path is None and args.run_dir:
        for name in ("pod_trace.json", "trace.worker0.json"):
            cand = os.path.join(args.run_dir, name)
            if os.path.exists(cand):
                trace_path = cand
                break
    if trace_path:
        trace_doc = load_trace(trace_path)
    flights = reconstruct(records, trace_doc)
    if not flights:
        print("flight: no serve_request events in "
              f"{metrics_path}", file=sys.stderr)
        return 2
    partition, attempt = find_partition(records)
    if attempt != 0:
        # see find_partition: cross-attempt reconciliation is the
        # drill verifier's job, not a bitwise identity
        partition = None
    res = verify(flights, partition)
    c = res["counts"]
    print(f"flight: {res['flights']} flights reconstructed — "
          f"admitted {c['admitted']} (done {c['completed']}, evicted "
          f"{c['evicted']}, lost {c['lost']}), shed "
          f"{c['shed_at_admission']}, expired {c['expired_in_queue']}, "
          f"rejected {c['rejected']}")
    print(f"flight: ttft decomposition worst "
          f"{res['ttft_decomp_worst_s']:.2e} s over "
          f"{res['decomposed']} admitted flights "
          f"(tol {res['ttft_decomp_tol_s']:.2e}, "
          f"{res['ttft_decomp_status']}); partition "
          f"{'reconciled' if res['partition_checked'] else 'not checked'}"
          f"; trace cross-checked {res['trace_checked']} flights")
    for p in res["problems"]:
        print(f"flight: PROBLEM: {p}", file=sys.stderr)
    print(f"flight: {'EXACT' if res['exact'] else 'INEXACT'}")
    return 0 if res["exact"] else 1


if __name__ == "__main__":
    sys.exit(main())
