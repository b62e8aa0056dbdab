"""Offline run report: ``python -m tpudist.obs.report --run-dir DIR``.

The acceptance-test philosophy (the container-HPC workflow of
arXiv:2208.02498) is that the run itself must emit the artifacts that
explain a failure. This CLI is the explainer: it ingests a finished
run's ``metrics.jsonl`` and merged ``pod_trace.json`` (plus an optional
baseline) and emits ``run_report.json`` + a human ``run_report.md``
with:

  * per-host, per-phase wall-time breakdown (SELF time: nested child
    spans are subtracted from their parents, so the phase totals are
    mutually exclusive and sum to the traced coverage of the run);
  * exposed-vs-overlapped staging time (``slab_wait`` spans = H2D the
    pipeline failed to hide; ``stage_slab`` = host staging work that
    overlapped compute);
  * DEVICE time (``--profile-window`` runs): the compute vs
    exposed-communication split recomputed from the device tracks
    merged into ``pod_trace.json`` (obs.devtime), exposed comm
    attributed to the host phase it occurred under, the
    ``comm_status`` verdict (``TPUDIST_COMM_EXPOSED_MAX``), and the
    delta against a baseline's exposed-comm fraction;
  * straggler attribution BY PHASE: not just "host 3 was slow" but
    which phase put it behind the pod median;
  * checkpoint-drain stalls (enqueue vs drain blocked time);
  * a regression verdict against a baseline steps/s;
  * the collective-sweep artifact (``--collectives
    BENCH_COLLECTIVES.json``): per-kind best bus bandwidth and % of
    ring peak, folded into the same report.

Offline by design: no jax import, no device touch — it runs on a
laptop against scp'd artifacts from a dead pod (obs.devtime, the only
tpudist import here, is jax-free for the same reason).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

from tpudist import rules as rules_lib
from tpudist.obs import devtime as devtime_mod
from tpudist.obs import goodput as goodput_mod
from tpudist.obs import live as live_mod
from tpudist.obs import memledger as memledger_mod
from tpudist.serve import flight as flight_mod
from tpudist.serve import slo as slo_mod

# Schema 5: adds the "goodput" section (cross-attempt wall-clock
# partition from the goodput ledger — tpudist.obs.goodput — or the
# run-end kind=goodput record for single-attempt runs).
# Schema 6: the serving section grows the resilience plane's exact
# shed partition (arrived/admitted/shed_at_admission/expired_in_queue/
# rejected/lost, shed_fraction + the serve_shed gate) and the
# degradation ladder's adapt_level/adapt_transitions; the Alerts
# cross-check adds the serve-gate table (rules.SERVE_STATUS_RULES).
# Schema 7: adds the "flights" section (per-request flight ledger from
# tpudist.serve.flight — chain-exactness verdict, bitwise ShedLedger
# reconciliation, TTFT decomposed into queue/prefill/decode components,
# spec-acceptance trajectory, shed/evict timeline); the serving section
# grows the PR 16 paged-footprint fields (kv_page_tokens /
# kv_pages_total / kv_pages_used_peak / kv_shared_refs,
# spec_accept_rate + the spec_accept gate, speculate_k,
# shared_prefix_len, active_slots_peak, verify_compiles).
# Schema 8: adds the "memory" section (per-device HBM ledger from
# tpudist.obs.memledger — exact params/opt_state/slabs/kv_pool/
# program_temp/headroom/residue partition, the hbm_headroom grade, and
# the per-bucket delta against a baseline's memory section).
REPORT_SCHEMA_VERSION = 8

# Artifact schemas this reader KNOWS. A newer number is a warning, not
# a failure: a requeue loop can scatter attempts across tpudist
# versions (the slice is re-provisioned, images drift), and a
# mixed-version attempt directory must still fold into ONE report —
# the known fields are read, unknown ones ignored.
KNOWN_ARTIFACT_SCHEMAS = {
    # mirrors obs.trace.TRACE_SCHEMA_VERSION — the one constant that
    # CANNOT be imported here (trace.py imports jax; this CLI must run
    # with jax uninstalled). tests/test_goodput.py diffs the two.
    "trace": 1,
    "alerts": live_mod.LIVE_SCHEMA_VERSION,
    "goodput": goodput_mod.GOODPUT_SCHEMA_VERSION,
    "memledger": memledger_mod.MEMLEDGER_SCHEMA_VERSION,
    "baseline": REPORT_SCHEMA_VERSION,
}


def warn_newer_schema(doc: Any, what: str,
                      known: Optional[int] = None) -> bool:
    """Forward-compat gate for every artifact this CLI loads: an
    artifact stamped with a schema NEWER than this reader knows gets a
    stderr warning and is read anyway (known fields only). Returns
    whether it warned (tests pin the path)."""
    if known is None:
        known = KNOWN_ARTIFACT_SCHEMAS[what]
    if not isinstance(doc, dict):
        return False
    s = doc.get("schema")
    if s is None:
        s = (doc.get("metadata") or {}).get("schema") \
            if isinstance(doc.get("metadata"), dict) else None
    if isinstance(s, (int, float)) and s > known:
        print(f"tpudist.obs.report: {what} artifact carries schema "
              f"{int(s)} > known {known} — reading the fields this "
              f"version knows, ignoring the rest (a mixed-version "
              f"attempt set still folds into one report)",
              file=sys.stderr)
        return True
    return False

SUCCESS = "success"
FAIL = "fail"
UNGATEABLE = "ungateable"

# Regression gate: measured steps/s below this fraction of baseline is
# a FAIL. Same advisory three-valued shape as the staging/straggler
# gates; override via --regress-min or TPUDIST_REGRESS_MIN. The value
# lives in tpudist.rules, shared with the live alert engine's regress
# rule so mid-run and offline grading cannot drift.
REGRESS_MIN_FRACTION = rules_lib.REGRESS_MIN_FRACTION

# A host whose per-phase self time exceeds the pod median by this many
# seconds AND this factor is attributed as a straggler cause.
ATTRIB_FACTOR = 1.25
ATTRIB_MIN_S = 0.05


# ----------------------------------------------------------- ingestion


def load_metrics(path: str) -> List[Dict[str, Any]]:
    recs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                recs.append(json.loads(line))
    return recs


def load_trace(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if "traceEvents" not in doc:
        raise ValueError(f"{path}: not a Chrome trace-event document "
                         f"(no traceEvents key)")
    warn_newer_schema(doc, "trace")
    return doc


def complete_events(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The 'X' (complete) events — the spans."""
    return [e for e in doc.get("traceEvents", [])
            if e.get("ph") == "X" and "ts" in e and "dur" in e]


# -------------------------------------------------- self-time analysis


def self_times(events: List[Dict[str, Any]]) -> Dict[int, Dict[str, Any]]:
    """Per-host phase breakdown from span SELF times.

    Spans on one thread nest properly (the tracer records them from a
    stack discipline), so each span's self time is its duration minus
    the time covered by its children; summing self times per category
    yields mutually-exclusive phase totals whose sum equals the union
    of traced time on that thread. Returns, per pid::

        {"wall_s", "covered_s", "coverage", "phases": {cat: s},
         "names": {name: {"s", "count"}}, "spans"}
    """
    by_host: Dict[int, Dict[str, Any]] = {}
    by_pid_tid: Dict[tuple, List[Dict[str, Any]]] = {}
    for e in events:
        by_pid_tid.setdefault((e.get("pid", 0), e.get("tid", 0)),
                              []).append(e)

    for (pid, _tid), evs in by_pid_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        host = by_host.setdefault(
            pid, {"t_min": None, "t_max": None,
                  "phases": {}, "names": {}, "spans": 0})
        # stack of [end_ts, child_covered_us] for open ancestors
        stack: List[List[float]] = []
        for e in evs:
            ts, dur = float(e["ts"]), float(e["dur"])
            end = ts + dur
            host["t_min"] = ts if host["t_min"] is None else min(
                host["t_min"], ts)
            host["t_max"] = end if host["t_max"] is None else max(
                host["t_max"], end)
            host["spans"] += 1
            while stack and stack[-1][0] <= ts + 1e-9:
                stack.pop()
            if stack:
                stack[-1][1] += dur     # covered inside the parent
            stack.append([end, 0.0])
            # self time resolves when the span closes; with sorted input
            # all children arrive before the next sibling, but their
            # durations accumulate into slot [1] as they are visited —
            # record a placeholder now and fix up after the pass
            e["_self_slot"] = stack[-1]
        for e in evs:
            ts, dur = float(e["ts"]), float(e["dur"])
            self_us = max(0.0, dur - e["_self_slot"][1])
            del e["_self_slot"]
            cat = e.get("cat", "misc")
            host["phases"][cat] = host["phases"].get(cat, 0.0) + self_us
            n = host["names"].setdefault(e.get("name", "?"),
                                         {"s": 0.0, "count": 0})
            n["s"] += dur / 1e6
            n["count"] += 1

    out: Dict[int, Dict[str, Any]] = {}
    for pid, h in sorted(by_host.items()):
        wall_us = ((h["t_max"] - h["t_min"])
                   if h["t_max"] is not None else 0.0)
        phases = {c: round(us / 1e6, 6) for c, us in
                  sorted(h["phases"].items(), key=lambda kv: -kv[1])}
        covered = sum(phases.values())
        out[pid] = {
            "wall_s": round(wall_us / 1e6, 6),
            "covered_s": round(covered, 6),
            "coverage": (round(covered / (wall_us / 1e6), 4)
                         if wall_us > 0 else None),
            "phases": phases,
            "names": {k: {"s": round(v["s"], 6), "count": v["count"]}
                      for k, v in sorted(h["names"].items(),
                                         key=lambda kv: -kv[1]["s"])},
            "spans": h["spans"],
        }
    return out


def _sum_named(events: List[Dict[str, Any]], *,
               names: Optional[set] = None,
               cat: Optional[str] = None,
               pid: Optional[int] = None) -> float:
    """Total duration (s) of spans matching name/cat/pid filters."""
    tot = 0.0
    for e in events:
        if names is not None and e.get("name") not in names:
            continue
        if cat is not None and e.get("cat") != cat:
            continue
        if pid is not None and e.get("pid") != pid:
            continue
        tot += float(e["dur"]) / 1e6
    return tot


# ----------------------------------------------------------- sections


def staging_section(events, timing: Optional[Dict]) -> Dict[str, Any]:
    """Exposed vs overlapped staging: ``slab_wait`` spans are the H2D
    the pipeline failed to hide behind compute; ``stage_slab`` is the
    host-side materialise+dispatch work that DID overlap."""
    exposed = _sum_named(events, names={"slab_wait"})
    staged = _sum_named(events, names={"stage_slab"})
    sec = {
        "exposed_wait_s": round(exposed, 6),
        "stage_host_s": round(staged, 6),
        "overlapped_s": round(max(0.0, staged - exposed), 6),
        "slabs": sum(1 for e in events if e.get("name") == "stage_slab"),
    }
    if timing:
        sec["timing_stage_wait_s"] = timing.get("stage_wait_s")
        sec["staging_status"] = timing.get("staging_status")
        sec["overlap_fraction"] = timing.get("staging_overlap_fraction")
    return sec


def ckpt_section(events, metrics) -> Dict[str, Any]:
    """Checkpoint cost split: per-save enqueue (what the step path
    paid) vs drain (time blocked on serialisation at wait/close)."""
    drains = [e for e in events if e.get("cat") == "ckpt"
              and "drain" in e.get("name", "")]
    enq = _sum_named(events, names={"ckpt_enqueue"})
    drain_recs = [r for r in metrics if r.get("kind") == "ckpt_drain"]
    saves = [r for r in metrics if r.get("kind") == "ckpt"]
    worst = max((float(e["dur"]) / 1e6 for e in drains), default=0.0)
    # what the enqueue spans counted where the snapshot happens: bytes
    # copied to the host, process CPU seconds spent meanwhile
    enq_args = [e.get("args") or {} for e in events
                if e.get("name") == "ckpt_enqueue"]
    return {
        "saves": len(saves),
        "enqueue_s": round(enq, 6),
        "enqueue_bytes": sum(int(a.get("bytes") or 0) for a in enq_args),
        "enqueue_cpu_s": round(sum(float(a.get("cpu_s") or 0.0)
                                   for a in enq_args), 6),
        "drain_s": round(sum(float(e["dur"]) / 1e6 for e in drains), 6),
        "drain_spans": len(drains),
        "worst_drain_s": round(worst, 6),
        "timing_drain_ms": (drain_recs[-1].get("drain_ms")
                            if drain_recs else None),
    }


# Exposed-comm phase attribution: host span categories in priority
# order — the most specific wins (a fence is inside an epoch; exposed
# comm during it is a DISPATCH finding, not a "train" finding). The
# "profile" cat (the capture-window bracket span itself) is excluded:
# it covers the whole window by construction and would absorb
# everything.
PHASE_PRIORITY = ("dispatch", "staging", "ckpt", "eval", "tune", "sync",
                  "data", "init", "train")


def _exposed_by_phase(exposed, host_evs) -> Dict[str, float]:
    """Attribute exposed-comm intervals (µs, merged) to the host phase
    they occurred under; leftovers (no span open, or only the capture
    bracket) read as ``other``."""
    by_cat: Dict[str, list] = {}
    for e in host_evs:
        cat = e.get("cat", "misc")
        if cat == "profile":
            continue
        ts, dur = float(e["ts"]), float(e["dur"])
        by_cat.setdefault(cat, []).append((ts, ts + dur))
    remaining = devtime_mod.merge_intervals(exposed)
    out: Dict[str, float] = {}
    extras = sorted(set(by_cat) - set(PHASE_PRIORITY))
    for cat in list(PHASE_PRIORITY) + extras:
        if cat not in by_cat or not remaining:
            continue
        hit = devtime_mod.intersect_intervals(remaining, by_cat[cat])
        s = devtime_mod.measure(hit) / 1e6
        if s > 0:
            out[cat] = round(s, 6)
        remaining = devtime_mod.subtract_intervals(remaining,
                                                   by_cat[cat])
    left = devtime_mod.measure(remaining) / 1e6
    if left > 0:
        out["other"] = round(left, 6)
    return out


def devtime_section(events, metrics, baseline: Optional[Dict]
                    ) -> Dict[str, Any]:
    """The device-time split: compute vs exposed communication per
    device track, recomputed from the device events a
    ``--profile-window`` run merged into ``pod_trace.json``
    (obs.devtime's interval math — the same operator the live run
    used), plus the per-phase attribution of exposed comm against the
    host spans, the ``comm_status`` verdict, and the exposed-fraction
    delta vs baseline. Falls back to the ``kind=devtime`` metrics
    record when the trace carries no device tracks (e.g. a ``--trace
    off`` run); ungateable when neither exists."""
    dev_evs = [e for e in events
               if e.get("cat") == devtime_mod.DEVTIME_CAT]
    host_evs = [e for e in events
                if e.get("cat") != devtime_mod.DEVTIME_CAT]
    recs = [r for r in metrics if r.get("kind") == "devtime"]

    devices: Dict[str, Any] = {}
    exposed_by_phase: Dict[str, float] = {}
    pod = {"compute_s": 0.0, "comm_s": 0.0, "exposed_comm_s": 0.0,
           "window_s": 0.0, "devices": 0, "exposed_comm_frac": None}
    if dev_evs:
        # per host: rebuild each device track's class intervals from
        # the coalesced compute/comm events
        by_pid: Dict[int, Dict[str, Dict[str, list]]] = {}
        for e in dev_evs:
            pid = e.get("pid", 0)
            dev = (e.get("args") or {}).get("device", str(e.get("tid")))
            cls = e.get("name")
            if cls not in ("compute", "comm"):
                continue
            ts, dur = float(e["ts"]), float(e["dur"])
            by_pid.setdefault(pid, {}).setdefault(
                dev, {"compute": [], "comm": []})[cls].append(
                    (ts, ts + dur))
        # window_s counts wall once per HOST (the capture window), while
        # the exposed fraction divides by DEVICE-seconds (window × each
        # host's device count) — the same convention as the live
        # kind=devtime record (devtime.attribute_tracks), so the report
        # and metrics.jsonl agree on both numbers
        win_host_us = 0.0
        win_dev_us = 0.0
        for pid, tracks in sorted(by_pid.items()):
            allv = [iv for c in tracks.values()
                    for ivs in c.values() for iv in ivs]
            window = (min(lo for lo, _ in allv),
                      max(hi for _, hi in allv)) if allv else None
            if window is not None:
                win_host_us += window[1] - window[0]
            exposed_pid: list = []
            for dev, classed in sorted(tracks.items()):
                att = devtime_mod.attribute_classed(classed, window)
                devices[f"host{pid}/{dev}"] = {
                    k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in att.items()}
                for k in ("compute_s", "comm_s", "exposed_comm_s"):
                    pod[k] += att[k]
                pod["devices"] += 1
                win_dev_us += att["window_s"] * 1e6
                exposed_pid.extend(devtime_mod.subtract_intervals(
                    classed["comm"], classed["compute"]))
            host_pid_evs = [e for e in host_evs if e.get("pid") == pid]
            for cat, s in _exposed_by_phase(exposed_pid,
                                            host_pid_evs).items():
                exposed_by_phase[cat] = round(
                    exposed_by_phase.get(cat, 0.0) + s, 6)
        pod["window_s"] = round(win_host_us / 1e6, 6)
        pod["exposed_comm_frac"] = (
            round(pod["exposed_comm_s"] * 1e6 / win_dev_us, 6)
            if win_dev_us > 0 else None)
        for k in ("compute_s", "comm_s", "exposed_comm_s"):
            pod[k] = round(pod[k], 6)
    elif recs:
        rec = recs[-1]
        for d in rec.get("per_device", []):
            devices[f"host{rec.get('process_index', 0)}/"
                    f"{d.get('device')}"] = {
                k: v for k, v in d.items() if k != "device"}
        for k in ("compute_s", "comm_s", "exposed_comm_s", "window_s",
                  "devices", "exposed_comm_frac"):
            if rec.get(k) is not None:
                pod[k] = rec[k]

    # fabric-graded at fold time: the record's axis_fabric label picks
    # the ICI or DCN ceiling (tpudist.rules.resolve_comm) — same
    # dispatch the live alert engine applied mid-run
    fabric = (recs[-1].get("fabric") if recs else None)
    status = devtime_mod.comm_status(pod["exposed_comm_frac"],
                                     fabric=fabric)
    base_frac = _find_exposed_frac(baseline) if baseline else None
    delta = (round(pod["exposed_comm_frac"] - base_frac, 6)
             if (pod["exposed_comm_frac"] is not None
                 and base_frac is not None) else None)
    out = {
        "comm_status": status,
        "fabric": fabric,
        "devices": devices,
        "pod": pod,
        "exposed_by_phase": exposed_by_phase,
        "record_comm_status": (recs[-1].get("comm_status")
                               if recs else None),
        "baseline_exposed_comm_frac": base_frac,
        "exposed_comm_frac_delta": delta,
    }
    # program-derived collective byte volumes (devtime.collective_bytes
    # rows carried on the record in both cross-slice modes): the DCN
    # bytes the schedule moves per step, surfaced next to the time split
    # they explain
    if recs and recs[-1].get("dcn_bytes_total") is not None:
        rec = recs[-1]
        out["dcn_bytes_total"] = rec["dcn_bytes_total"]
        out["ici_bytes_total"] = rec.get("ici_bytes_total")
        out["collectives"] = rec.get("collectives")
    # device seconds per program scope (tpudist.scopes), from the
    # capture's own name stacks: which LAYER the device time went to
    if recs and recs[-1].get("by_scope"):
        out["by_scope"] = recs[-1]["by_scope"]
    return out


def _find_exposed_frac(doc: Any) -> Optional[float]:
    """Dig an exposed-comm fraction out of a baseline document: a prior
    run_report (``devtime.pod.exposed_comm_frac``) or a bare pin."""
    if not isinstance(doc, dict):
        return None
    for path in (("exposed_comm_frac",),
                 ("devtime", "pod", "exposed_comm_frac")):
        cur: Any = doc
        for k in path:
            cur = cur.get(k) if isinstance(cur, dict) else None
        if isinstance(cur, (int, float)):
            return float(cur)
    return None


def collectives_section(doc: Optional[Dict]) -> Optional[Dict[str, Any]]:
    """Fold BENCH_COLLECTIVES.json (``python -m tpudist.bench.sweep
    --bench-out``) into the report: per collective kind, the
    best-bucket bus bandwidth and % of ring peak. Purely informational — the sweep gate already ran
    live; this puts the numbers next to the exposed-comm split they
    explain."""
    if not doc:
        return None
    detail = doc.get("detail", doc)
    rows = detail.get("rows", [])
    per_kind: Dict[str, Dict[str, Any]] = {}
    for r in rows:
        # tolerate truncated/hand-kept artifacts (this CLI's offline
        # contract): a row without a kind or bandwidth is skipped, not
        # a traceback
        kind = r.get("kind")
        gbps = r.get("bus_gbps")
        if kind is None or not isinstance(gbps, (int, float)):
            continue
        best = per_kind.get(kind)
        if best is None or gbps > best["bus_gbps"]:
            per_kind[kind] = {
                "bus_gbps": gbps,
                "pct_of_ring_peak": r.get("pct_of_ring_peak"),
                "message_bytes": r.get("message_bytes"),
                "fabric": r.get("fabric"),
            }
    return {
        "axis": detail.get("axis"),
        "fabric": detail.get("fabric"),
        "n_devices": detail.get("n_devices"),
        "rows": len(rows),
        "per_kind": per_kind,
    }


# At-exit fail verdicts and the live alert rule that should have fired
# for each — the Alerts section's cross-check table. The whole point of
# on-line alerting is that a run which grades fail at exit alerted
# HOURS earlier; a fail with no matching mid-run alert is a gap in the
# live engine's coverage and gets flagged as a report warning. The
# table itself lives in tpudist.rules (shared with the chaos verifier's
# end-to-end pin of the same invariant) so the two checkers cannot
# drift.
_EXIT_FAIL_TO_RULE = rules_lib.STATUS_RULES


def alerts_section(metrics: List[Dict[str, Any]],
                   alert_history: Optional[List[Dict[str, Any]]],
                   timing: Optional[Dict]) -> Dict[str, Any]:
    """The live-telemetry slice of the report: the alert fire/resolve
    history (first-fire step/time, duration, final state per
    ``(rule, host)``) plus the on-line/at-exit parity cross-check.

    ``alert_history`` comes from ``alerts.jsonl`` (the aggregator's
    append-only transition log) or ``live_status.json``; runs without
    the live bus fall back to the ``kind=alert`` records the aggregator
    mirrored into ``metrics.jsonl``; a run with neither reads as
    ``enabled: False`` and skips the cross-check (nothing was watching,
    so a miss means nothing)."""
    history = list(alert_history or [])
    live_seen = alert_history is not None
    if not history:
        history = [r for r in metrics if r.get("kind") == "alert"]
        live_seen = live_seen or bool(history)
    # fold transitions into one row per (rule, host): the FIRING event
    # pins first_step/first_ts; the latest transition wins the rest
    rows: Dict[tuple, Dict[str, Any]] = {}
    for rec in history:
        rule = rec.get("alert")
        if not rule:
            continue
        key = (rule, rec.get("host"))
        row = rows.setdefault(key, {
            "alert": rule, "host": rec.get("host"),
            "first_step": rec.get("first_step"),
            "first_ts": rec.get("first_ts"),
            "state": rec.get("state"), "duration_s": 0.0,
            "value": rec.get("value"),
            "threshold": rec.get("threshold")})
        row["state"] = rec.get("state", row["state"])
        for k in ("value", "threshold"):
            if rec.get(k) is not None:
                row[k] = rec[k]
        if rec.get("duration_s") is not None:
            row["duration_s"] = max(row["duration_s"],
                                    float(rec["duration_s"]))
    fired_rules = {r["alert"] for r in rows.values()}
    warnings = []
    if live_seen:
        for status_key, rule in _EXIT_FAIL_TO_RULE:
            if (timing or {}).get(status_key) == FAIL \
                    and rule not in fired_rules:
                warnings.append(
                    f"at-exit {status_key}=fail had NO mid-run "
                    f"{rule!r} alert — live coverage gap")
        # the serve lane's twin of the same invariant: a kind=serve
        # summary that graded a gate fail must have its mid-run alert
        # (rules.SERVE_STATUS_RULES — shared with the serve drill
        # verifier, tpudist.serve.drill)
        serve = next((r for r in reversed(metrics)
                      if r.get("kind") == "serve"), None)
        if serve is not None:
            for status_key, rule in rules_lib.SERVE_STATUS_RULES:
                if serve.get(status_key) == FAIL \
                        and rule not in fired_rules:
                    warnings.append(
                        f"at-exit serve {status_key}=fail had NO "
                        f"mid-run {rule!r} alert — live coverage gap")
        # a watchdog stall dump in the stream means the run wedged;
        # the live stall alert must have fired before the kill
        if any(r.get("kind") == "stall_dump" for r in metrics) \
                and "stall" not in fired_rules:
            warnings.append("watchdog stall dump recorded but NO "
                            "mid-run 'stall' alert fired")
    return {
        "enabled": live_seen,
        "events": len(history),
        "history": sorted(rows.values(),
                          key=lambda r: (r.get("first_ts") or 0)),
        "fired_rules": sorted(fired_rules),
        "warnings": warnings,
    }


def straggler_section(hosts: Dict[int, Dict[str, Any]],
                      metrics) -> Dict[str, Any]:
    """Straggler attribution BY PHASE: for each host, which phase's
    self time exceeds the pod median of that phase. With < 2 hosts
    there is nothing to compare — ungateable, like the live verdict."""
    import statistics
    hosts_rec = [r for r in metrics if r.get("kind") == "hosts"]
    status = (hosts_rec[-1].get("straggler_status")
              if hosts_rec else UNGATEABLE)
    if len(hosts) < 2:
        return {"status": status if hosts_rec else UNGATEABLE,
                "attribution": []}
    cats = sorted({c for h in hosts.values() for c in h["phases"]})
    attribution = []
    for cat in cats:
        vals = {pid: h["phases"].get(cat, 0.0)
                for pid, h in hosts.items()}
        med = statistics.median(vals.values())
        for pid, v in vals.items():
            if v > ATTRIB_FACTOR * med and v - med > ATTRIB_MIN_S:
                attribution.append({
                    "process": pid, "phase": cat,
                    "self_s": round(v, 6),
                    "pod_median_s": round(med, 6),
                    "excess_s": round(v - med, 6)})
    attribution.sort(key=lambda a: -a["excess_s"])
    return {"status": status, "attribution": attribution}


def regression_section(timing: Optional[Dict],
                       baseline: Optional[Dict],
                       min_fraction: float) -> Dict[str, Any]:
    """Measured steps/s vs baseline. Baseline JSON: any dict carrying
    ``steps_per_sec`` (a prior run_report.json, a BENCH row, or a
    hand-written pin). No baseline / no measurement → ungateable."""
    measured = None
    if timing and timing.get("run_s") and timing.get("steps"):
        measured = timing["steps"] / timing["run_s"]
    base = _find_steps_per_sec(baseline) if baseline else None
    if measured is None or base is None or base <= 0:
        return {"status": UNGATEABLE, "steps_per_sec": measured,
                "baseline_steps_per_sec": base, "ratio": None,
                "min_fraction": min_fraction}
    ratio = measured / base
    return {"status": SUCCESS if ratio >= min_fraction else FAIL,
            "steps_per_sec": round(measured, 4),
            "baseline_steps_per_sec": round(base, 4),
            "ratio": round(ratio, 4), "min_fraction": min_fraction}


def serving_section(metrics: List[Dict[str, Any]],
                    baseline: Optional[Dict] = None,
                    events: Sequence[Dict[str, Any]] = ()
                    ) -> Dict[str, Any]:
    """The serving slice of the report (tpudist.serve): the run's
    latency percentiles and throughput RE-GRADED through the shared SLO
    gates (tpudist.serve.slo over the rules table — same thresholds the
    serve loop's on-line alerts and exit verdict applied, env read at
    fold time), queue depth over time from the ``kind=serve_tick``
    stream, an optional throughput comparison against a baseline
    BENCH_SERVE.json / prior report, and from the trace's
    ``weights_resident`` spans what the engine converted to hold the
    weights at rest in its dtype. Runs without serve records read as
    ``enabled: False`` — a training run has no SLO to grade."""
    serves = [r for r in metrics if r.get("kind") == "serve"]
    if not serves:
        return {"enabled": False}
    s = serves[-1]
    graded = slo_mod.grade(s.get("ttft_p99_s"), s.get("itl_p99_s"),
                           s.get("tokens_per_sec_per_chip"),
                           shed_fraction=s.get("shed_fraction"))
    ticks = [r for r in metrics if r.get("kind") == "serve_tick"]
    queue = [{"t_s": r.get("t_s"), "queue_depth": r.get("queue_depth"),
              "active_slots": r.get("active_slots"),
              "completed": r.get("completed")} for r in ticks]
    tunes = [r for r in metrics if r.get("kind") == "serve_tune"]
    base_tps = _find_serve_tps(baseline) if baseline else None
    tps = s.get("tokens_per_sec_per_chip")
    ratio = (round(tps / base_tps, 4)
             if isinstance(tps, (int, float)) and base_tps else None)
    resident = [e for e in events if e.get("name") == "weights_resident"]
    return {
        "enabled": True,
        "status": graded["status"],
        "gates": {rule: graded[f"{rule}_status"]
                  for rule, _ in slo_mod.SERVE_RULES},
        "thresholds": {rule: rules_lib.resolve(rule)
                       for rule, _ in slo_mod.SERVE_RULES},
        "requests": s.get("requests"), "completed": s.get("completed"),
        "generated_tokens": s.get("generated_tokens"),
        "truncated": s.get("truncated"), "wall_s": s.get("wall_s"),
        "slots": s.get("slots"), "decode_k": s.get("decode_k"),
        "kv_cache_bytes": s.get("kv_cache_bytes"),
        "tokens_per_sec": s.get("tokens_per_sec"),
        "tokens_per_sec_per_chip": tps,
        "ttft_p50_s": s.get("ttft_p50_s"),
        "ttft_p99_s": s.get("ttft_p99_s"),
        "itl_p50_s": s.get("itl_p50_s"),
        "itl_p99_s": s.get("itl_p99_s"),
        "e2e_p99_s": s.get("e2e_p99_s"),
        "prefill_compiles": s.get("prefill_compiles"),
        "decode_compiles": s.get("decode_compiles"),
        "verify_compiles": s.get("verify_compiles"),
        "queue_depth_max": s.get("queue_depth_max"),
        "queue_over_time": queue,
        "active_slots_peak": s.get("active_slots_peak"),
        # the PR 16 paged footprint + speculation fields: what the pool
        # actually held at peak and how well the draft guessed. The
        # spec_accept gate re-grades here like every other gate (env
        # read at fold time); pre-paged artifacts read None/absent
        "kv_page_tokens": s.get("kv_page_tokens"),
        "kv_pages_total": s.get("kv_pages_total"),
        "kv_pages_used_peak": s.get("kv_pages_used_peak"),
        "kv_pages_used_mean": s.get("kv_pages_used_mean"),
        # two kinds of cache state and the routed experts' load (a model
        # with window layers and experts held here; None elsewhere)
        "kv_window_tokens_total": s.get("kv_window_tokens_total"),
        "kv_window_tokens_peak": s.get("kv_window_tokens_peak"),
        "moe_pairs_per_expert_mean": s.get("moe_pairs_per_expert_mean"),
        "moe_experts_hit_mean": s.get("moe_experts_hit_mean"),
        # blocks of rows the expert routine ran (its loop's trips or its
        # kernel's tiles), and from the engine's ``experts_path`` instant
        # which of the two its dispatch program was lowered with
        "moe_blocks_mean": s.get("moe_blocks_mean"),
        # a model with identity experts (None elsewhere): their pairs and
        # all pairs routed, a layer a token step, and the former's share
        "moe_pairs_zero_mean": s.get("moe_pairs_zero_mean"),
        "moe_pairs_all_mean": s.get("moe_pairs_all_mean"),
        "moe_zero_share": s.get("moe_zero_share"),
        "experts_path": next(
            ((e.get("args") or {}).get("path") for e in events
             if e.get("name") == "experts_path"), None),
        # a model that generates by diffusion over blocks (None elsewhere)
        "block_length": s.get("block_length"),
        "forwards_per_token": s.get("forwards_per_token"),
        "commits_fused": s.get("commits_fused"),
        "forwards_launched": s.get("forwards_launched"),
        "tokens_per_dispatch": s.get("tokens_per_dispatch"),
        # one span a params tree the engine had to convert (none where
        # the tree rests in the engine's dtype already)
        "weights_resident": ({
            "trees": len(resident),
            "seconds": round(sum(float(e["dur"]) for e in resident) / 1e6,
                             6),
            **{k: sum(int((e.get("args") or {}).get(k) or 0)
                      for e in resident)
               for k in ("leaves", "leaves_cast", "bytes_in",
                         "bytes_out")}} if resident else None),
        "spec_accept_rate": s.get("spec_accept_rate"),
        "spec_accept_status": slo_mod.rule_status(
            "spec_accept", s.get("spec_accept_rate")),
        "speculate_k": s.get("speculate_k"),
        "shared_prefix_len": s.get("shared_prefix_len"),
        # the resilience plane's exact shed partition (PR 15): absent
        # keys on pre-resilience artifacts simply read None
        "arrived": s.get("arrived"), "admitted": s.get("admitted"),
        "shed_at_admission": s.get("shed_at_admission"),
        "expired_in_queue": s.get("expired_in_queue"),
        "rejected": s.get("rejected"), "lost": s.get("lost"),
        "shed_fraction": s.get("shed_fraction"),
        "queue_cap": s.get("queue_cap"),
        "ttft_deadline_s": s.get("ttft_deadline_s"),
        "adapt_level": s.get("adapt_level"),
        "adapt_transitions": [
            {k: r.get(k) for k in ("t_s", "from_level", "to_level",
                                   "decode_k", "reason")}
            for r in metrics if r.get("kind") == "serve_adapt"],
        "tuning": ({k: tunes[-1].get(k) for k in
                    ("status", "source", "trials", "decode_k",
                     "kv_page_tokens", "speculate_k")}
                   if tunes else None),
        "baseline_tokens_per_sec_per_chip": base_tps,
        "tokens_per_chip_ratio": ratio,
    }


def flights_section(metrics: List[Dict[str, Any]],
                    trace_doc: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """The request-flight slice (tpudist.serve.flight): every arrived
    rid reconstructed into its lifecycle chain and verified EXACTLY —
    one admission verdict, one terminal state, TTFT equal to its own
    queue/prefill decomposition within the flight_decomp tolerance, and
    chain counts reconciled bitwise against the ShedLedger partition
    (attempt 0 only — a resumed attempt's ledger partitions only its
    own arrivals while the replayed event stream spans every attempt).
    Plus the aggregates the chains make possible: p50/p99 of each TTFT
    component, the speculative-acceptance trajectory, and the
    shed/evict timeline. Runs without ``kind=serve_request`` records
    read as ``enabled: False``."""
    if not any(r.get("kind") == "serve_request" for r in metrics):
        return {"enabled": False}
    flights = flight_mod.reconstruct(metrics, trace_doc)
    partition, attempt = flight_mod.find_partition(metrics)
    res = flight_mod.verify(flights,
                            partition if attempt == 0 else None)
    spec = [{"t_s": r.get("t_s"),
             "spec_accept_rate": r.get("spec_accept_rate")}
            for r in metrics if r.get("kind") == "serve_tick"
            and r.get("spec_accept_rate") is not None]
    return {
        "enabled": True,
        "exact": res["exact"],
        "flights": res["flights"],
        "counts": res["counts"],
        "partition_checked": res["partition_checked"],
        "trace_checked": res["trace_checked"],
        "decomposed": res["decomposed"],
        "ttft_decomp_worst_s": res["ttft_decomp_worst_s"],
        "ttft_decomp_tol_s": res["ttft_decomp_tol_s"],
        "ttft_decomp_status": res["ttft_decomp_status"],
        "decomposition": flight_mod.decomposition(flights),
        "spec_accept_over_time": spec,
        "shed_timeline": flight_mod.shed_timeline(flights),
        # bounded: a pathological run could break every chain, and the
        # report must stay readable — the flight CLI prints them all
        "problems": res["problems"][:20],
        "problem_count": len(res["problems"]),
    }


def goodput_section(metrics: List[Dict[str, Any]],
                    ledger: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """The goodput slice of the report (tpudist.obs.goodput): the
    cross-attempt wall-clock partition when a ledger is available
    (attempts.jsonl next to the artifacts, or a prebuilt goodput.json),
    else the run-end ``kind=goodput`` attempt-local estimate. The
    status is RE-GRADED through the shared rules table at fold time
    (env read now — same discipline as the serving section), but the
    fraction itself is the ledger's verbatim: the CLI, this section and
    the Prometheus gauges must report the identical number (the
    consumer-parity pin in tests/test_goodput.py)."""
    if ledger:
        frac = ledger.get("goodput_fraction")
        return {
            "enabled": True,
            "cross_attempt": True,
            "status": goodput_mod.goodput_status(frac),
            "fraction": frac,
            "min_fraction": rules_lib.resolve("goodput"),
            "total_wall_s": ledger.get("total_wall_s"),
            "buckets": ledger.get("totals"),
            "lost_steps": ledger.get("lost_steps"),
            "exact": ledger.get("exact"),
            "tolerance": ledger.get("tolerance"),
            "problems": ledger.get("problems") or [],
            "attempts": [
                {k: a.get(k) for k in
                 ("attempt", "wall_s", "rc", "verdict", "steps_done",
                  "lost_steps", "steps_per_sec", "buckets")}
                for a in ledger.get("attempts", [])],
        }
    recs = [r for r in metrics if r.get("kind") == "goodput"]
    if not recs:
        return {"enabled": False}
    g = recs[-1]
    return {
        "enabled": True,
        "cross_attempt": False,
        "status": goodput_mod.goodput_status(g.get("fraction")),
        "fraction": g.get("fraction"),
        "min_fraction": rules_lib.resolve("goodput"),
        "total_wall_s": g.get("wall_s"),
        "buckets": {k: g.get(f"{k}_s") for k in goodput_mod.BUCKETS
                    if g.get(f"{k}_s") is not None},
        "lost_steps": None,
        "exact": None,
        "attempts": [{"attempt": g.get("requeue_attempt"),
                      "wall_s": g.get("wall_s")}],
    }


def _find_memory_buckets(doc: Any) -> Optional[Dict[str, Any]]:
    """Dig a per-bucket byte map out of a baseline document: a raw
    memledger.json (top-level ``buckets``) or a prior run_report's
    memory section."""
    if not isinstance(doc, dict):
        return None
    for path in (("buckets",), ("memory", "buckets")):
        cur: Any = doc
        for k in path:
            cur = cur.get(k) if isinstance(cur, dict) else None
        if isinstance(cur, dict) and cur:
            return cur
    return None


def memory_section(metrics: List[Dict[str, Any]],
                   ledger: Optional[Dict[str, Any]] = None,
                   baseline: Optional[Dict] = None) -> Dict[str, Any]:
    """The HBM-ledger slice of the report (tpudist.obs.memledger): the
    exact per-bucket partition of one device's HBM, graded against the
    shared ``hbm_headroom`` floor at fold time (env read now — same
    re-grade discipline as the goodput section), plus the per-bucket
    delta when the baseline carries a memory section of its own. A run
    with neither a ``memledger.json`` artifact nor a ``kind=memledger``
    record folds to ``{"enabled": False}`` — UNGATEABLE, never a crash
    (older run dirs predate the ledger)."""
    if ledger is None:
        recs = [r for r in metrics if r.get("kind") == "memledger"]
        if recs:
            ledger = memledger_mod.from_record(recs[-1])
    if not ledger:
        return {"enabled": False, "status": UNGATEABLE}
    frac = ledger.get("headroom_fraction")
    buckets = {k: (ledger.get("buckets") or {}).get(k)
               for k in memledger_mod.BUCKETS}
    sec: Dict[str, Any] = {
        "enabled": True,
        "status": memledger_mod.hbm_headroom_status(frac),
        "headroom_fraction": frac,
        "min_fraction": rules_lib.resolve("hbm_headroom"),
        "mode": ledger.get("mode"),
        "total_hbm_bytes": ledger.get("total_hbm_bytes"),
        "buckets": buckets,
        "watermark_bytes": ledger.get("watermark_bytes"),
        "watermark_source": ledger.get("watermark_source"),
        "program_temp_complete": ledger.get("program_temp_complete"),
        "programs": sorted((ledger.get("programs") or {}).keys()),
        "exact": ledger.get("exact"),
        "problems": ledger.get("problems") or [],
        "notes": ledger.get("notes") or [],
    }
    base_buckets = _find_memory_buckets(baseline)
    if base_buckets:
        sec["bucket_delta_bytes"] = {
            k: int(buckets.get(k) or 0) - int(base_buckets.get(k) or 0)
            for k in memledger_mod.BUCKETS
            if buckets.get(k) is not None
            or base_buckets.get(k) is not None}
    return sec


def _find_serve_tps(doc: Any) -> Optional[float]:
    """Dig a serve tokens/s/chip baseline out of a document: a
    BENCH_SERVE.json (top-level ``value`` under the serve metric name),
    a prior run_report's serving section, or a bare number under
    ``tokens_per_sec_per_chip``."""
    if not isinstance(doc, dict):
        return None
    if doc.get("metric") == "serve_tokens_per_sec_per_chip" \
            and isinstance(doc.get("value"), (int, float)):
        return float(doc["value"])
    for path in (("tokens_per_sec_per_chip",),
                 ("serving", "tokens_per_sec_per_chip")):
        cur: Any = doc
        for k in path:
            cur = cur.get(k) if isinstance(cur, dict) else None
        if isinstance(cur, (int, float)) and cur > 0:
            return float(cur)
    return None


def _find_steps_per_sec(doc: Any) -> Optional[float]:
    """Dig a steps/s number out of a baseline document: top-level
    ``steps_per_sec``, a run_report's ``regression.steps_per_sec``, or
    a ``run.steps_per_sec``."""
    if not isinstance(doc, dict):
        return None
    for path in (("steps_per_sec",),
                 ("run", "steps_per_sec"),
                 ("regression", "steps_per_sec")):
        cur: Any = doc
        for k in path:
            cur = cur.get(k) if isinstance(cur, dict) else None
        if isinstance(cur, (int, float)) and cur > 0:
            return float(cur)
    return None


# -------------------------------------------------------- the report


def build_report(metrics: List[Dict[str, Any]],
                 trace_doc: Dict[str, Any], *,
                 baseline: Optional[Dict] = None,
                 regress_min: Optional[float] = None,
                 collectives: Optional[Dict] = None,
                 alert_history: Optional[List[Dict]] = None,
                 goodput: Optional[Dict] = None,
                 memledger: Optional[Dict] = None
                 ) -> Dict[str, Any]:
    if regress_min is None:
        # the shared rules table (same env knob, read at call time, as
        # the live alert engine's regress rule)
        regress_min = rules_lib.resolve("regress")
    all_events = complete_events(trace_doc)
    # the host-side analyses must not see the device tracks: a device
    # busy interval is not a host phase, and folding it into self-time
    # would double every covered second of a profiled window
    events = [e for e in all_events
              if e.get("cat") != devtime_mod.DEVTIME_CAT]
    hosts = self_times(events)
    timings = [r for r in metrics if r.get("kind") == "timing"]
    timing = timings[-1] if timings else None
    epochs = [r for r in metrics if r.get("kind") == "epoch"]
    tunes = [r for r in metrics if r.get("kind") == "tune"]
    resumes = [r for r in metrics if r.get("kind") == "resume"]
    resume = resumes[-1] if resumes else None

    regression = regression_section(timing, baseline, regress_min)
    stragglers = straggler_section(hosts, metrics)
    devtime = devtime_section(all_events, metrics, baseline)
    alerts = alerts_section(metrics, alert_history, timing)
    serving = serving_section(metrics, baseline, events)
    flights = flights_section(metrics, trace_doc)
    goodput_sec = goodput_section(metrics, goodput)
    memory = memory_section(metrics, memledger, baseline)
    # the correlation id: every metrics record carries it (the train
    # CLI stamps MetricsLogger.extra); older artifacts fall back to the
    # trace metadata
    run_id = next((r.get("run_id") for r in metrics if r.get("run_id")),
                  None) or trace_doc.get("metadata", {}).get("run_id")
    # pod-level phase totals (sum over hosts)
    pod_phases: Dict[str, float] = {}
    for h in hosts.values():
        for c, s in h["phases"].items():
            pod_phases[c] = pod_phases.get(c, 0.0) + s

    # a serving section whose gates all read ungateable measured
    # NOTHING — it must not count as evidence toward a success verdict
    # (the serve CLI's own exit verdict for that run is ungateable)
    serving_measured = serving["enabled"] \
        and serving["status"] != UNGATEABLE
    verdict = SUCCESS
    if regression["status"] == FAIL or stragglers["status"] == FAIL \
            or (serving["enabled"] and serving["status"] == FAIL):
        verdict = FAIL
    elif not events and not serving_measured:
        verdict = UNGATEABLE

    return {
        "schema": REPORT_SCHEMA_VERSION,
        "run": {
            "run_id": run_id,
            "steps": timing.get("steps") if timing else None,
            "run_s": timing.get("run_s") if timing else None,
            "compile_warmup_s": (timing.get("compile_warmup_s")
                                 if timing else None),
            "steps_per_sec": regression["steps_per_sec"],
            "epochs": len(epochs),
            "final_avg_loss": (epochs[-1].get("avg_loss")
                               if epochs else None),
            "staging_status": (timing.get("staging_status")
                               if timing else None),
            "tuning_status": (tunes[-1].get("status") if tunes
                              else (timing or {}).get("tuning_status")),
            "straggler_status": stragglers["status"],
            "comm_status": devtime["comm_status"],
            "trace_status": (timing.get("trace_status")
                             if timing else None),
            # elastic-resume slice of the header (tpudist.elastic): did
            # this run continue a preempted one, from where, at what cost
            "resume_status": ((resume or {}).get("status")
                              or (timing or {}).get("resume_status")),
            "resumed_from_step": (resume or {}).get("resumed_from_step"),
            "resume_steps_lost": (resume or {}).get("steps_lost"),
            "requeue_attempt": (resume or {}).get("requeue_attempt"),
        },
        "trace": {
            "hosts": trace_doc.get("metadata", {}).get("hosts", 1),
            "spans": len(events),
            "dropped": trace_doc.get("metadata", {}).get("dropped", 0),
            "clock_offsets_ns": trace_doc.get("metadata", {}).get(
                "clock_offsets_ns"),
        },
        "hosts": {str(pid): h for pid, h in hosts.items()},
        "pod_phases": {c: round(s, 6) for c, s in
                       sorted(pod_phases.items(), key=lambda kv: -kv[1])},
        "staging": staging_section(events, timing),
        "ckpt": ckpt_section(events, metrics),
        "devtime": devtime,
        "collectives": collectives_section(collectives),
        "stragglers": stragglers,
        "regression": regression,
        "serving": serving,
        "flights": flights,
        "goodput": goodput_sec,
        "memory": memory,
        "alerts": alerts,
        "verdict": verdict,
    }


def to_markdown(report: Dict[str, Any]) -> str:
    """The human half of the artifact pair."""
    r = report
    lines = ["# tpudist run report", ""]
    run = r["run"]
    if run.get("run_id"):
        att = run.get("requeue_attempt")
        lines += [f"_run {run['run_id']}"
                  + (f" · requeue attempt {att}" if att else "") + "_",
                  ""]
    lines += [f"**Verdict: {r['verdict']}** — regression "
              f"{r['regression']['status']}, stragglers "
              f"{r['stragglers']['status']}, staging "
              f"{run.get('staging_status')}, tuning "
              f"{run.get('tuning_status')}", ""]
    if run.get("run_s"):
        sps = run.get("steps_per_sec")
        warm = run.get("compile_warmup_s")
        lines += [f"- steady-state: {run['steps']} steps in "
                  f"{run['run_s']:.3f}s"
                  + (f" ({sps:.2f} steps/s)" if sps else ""),
                  f"- compile+warmup: "
                  + (f"{warm:.3f}s" if warm is not None else "—"),
                  f"- epochs: {run['epochs']}, final avg loss "
                  f"{run.get('final_avg_loss')}", ""]
    if run.get("resume_status") not in (None, UNGATEABLE):
        lost = run.get("resume_steps_lost")
        req = run.get("requeue_attempt")
        req_note = f", requeue attempt {req}" if req else ""
        if run["resume_status"] == FAIL:
            # a failed restore means the run started FRESH — saying
            # "continued from step 0" would claim a continuation that
            # never happened
            lines += [f"- resume: **fail** — restore errored, run "
                      f"started fresh{req_note}", ""]
        else:
            lines += [f"- resume: **{run['resume_status']}** — continued "
                      f"from global step {run.get('resumed_from_step')}"
                      + (f", ~{lost} step(s) lost to the preemption"
                         if lost is not None else "")
                      + req_note, ""]
    reg = r["regression"]
    if reg["status"] != UNGATEABLE:
        lines += [f"- regression gate: {reg['steps_per_sec']} vs baseline "
                  f"{reg['baseline_steps_per_sec']} steps/s (ratio "
                  f"{reg['ratio']}, floor {reg['min_fraction']}) → "
                  f"**{reg['status']}**", ""]
    lines += ["## Per-host phase breakdown (span self time)", ""]
    cats = list(r["pod_phases"].keys())
    lines += ["| host | wall s | coverage | "
              + " | ".join(cats) + " |",
              "|---|---|---|" + "---|" * len(cats)]
    for pid, h in r["hosts"].items():
        row = [f"host{pid}", f"{h['wall_s']:.3f}",
               f"{h['coverage']:.0%}" if h["coverage"] else "—"]
        row += [f"{h['phases'].get(c, 0.0):.3f}" for c in cats]
        lines.append("| " + " | ".join(row) + " |")
    lines.append("")
    st = r["staging"]
    lines += ["## Staging",
              f"- exposed H2D wait: {st['exposed_wait_s']:.3f}s over "
              f"{st['slabs']} slabs; host staging work "
              f"{st['stage_host_s']:.3f}s "
              f"(overlapped ≈ {st['overlapped_s']:.3f}s)", ""]
    ck = r["ckpt"]
    lines += ["## Checkpointing",
              f"- {ck['saves']} saves, enqueue {ck['enqueue_s']:.3f}s, "
              f"drain {ck['drain_s']:.3f}s over {ck['drain_spans']} "
              f"drain windows (worst {ck['worst_drain_s']:.3f}s)"]
    if ck.get("enqueue_bytes") and ck["enqueue_s"] > 0:
        # cores busy near the snapshot's thread count: the copy is slow;
        # far under it: the process was starved of its cores
        lines += [f"- enqueue snapshots: "
                  f"{ck['enqueue_bytes'] / 1e9:.3f} GB to the host, "
                  f"{ck['enqueue_cpu_s']:.2f} CPU-s "
                  f"({ck['enqueue_cpu_s'] / ck['enqueue_s']:.1f} cores "
                  f"busy)"]
    lines += [""]
    dt = r.get("devtime") or {}
    if dt.get("devices"):
        pod = dt["pod"]
        lines += ["## Device time (compute vs exposed communication)",
                  "",
                  f"**comm_status: {dt['comm_status']}**"
                  + (f" ({dt['fabric']}-graded)"
                     if dt.get("fabric") else "")
                  + f" — exposed "
                  f"comm {pod['exposed_comm_s']:.3f}s summed over "
                  f"{pod['devices']} device track(s), "
                  f"{100 * (pod['exposed_comm_frac'] or 0):.1f}% of "
                  f"device time in a {pod['window_s']:.3f}s window"
                  + (f", baseline "
                     f"{100 * dt['baseline_exposed_comm_frac']:.1f}% "
                     f"(delta "
                     f"{100 * dt['exposed_comm_frac_delta']:+.1f}pp)"
                     if dt.get("exposed_comm_frac_delta") is not None
                     else ""), "",
                  "| device | compute s | comm s | exposed s | idle % |",
                  "|---|---|---|---|---|"]
        for name, d in dt["devices"].items():
            idle = d.get("idle_frac")
            lines.append(
                f"| {name} | {d['compute_s']:.3f} | {d['comm_s']:.3f} "
                f"| {d['exposed_comm_s']:.3f} | "
                + (f"{100 * idle:.1f} |" if idle is not None else "— |"))
        lines.append("")
        if dt.get("exposed_by_phase"):
            lines.append("- exposed comm by host phase: " + ", ".join(
                f"{cat} {s:.3f}s"
                for cat, s in dt["exposed_by_phase"].items()))
            lines.append("")
        if dt.get("by_scope"):
            tot = sum(dt["by_scope"].values()) or 1.0
            lines.append("- device time by program scope: " + ", ".join(
                f"{name or '(no scope)'} {sec:.3f}s "
                f"({100 * sec / tot:.1f}%)"
                for name, sec in dt["by_scope"].items()))
            lines.append("")
        if dt.get("dcn_bytes_total") is not None:
            lines.append(
                f"- collective bytes per step (program-derived): "
                f"{dt['dcn_bytes_total']} B over DCN, "
                f"{dt.get('ici_bytes_total') or 0} B over ICI "
                f"({len(dt.get('collectives') or [])} op group(s))")
            lines.append("")
    co = r.get("collectives")
    if co and co.get("per_kind"):
        lines += ["## Collectives (bench sweep)", "",
                  "| kind | fabric | best bus GB/s | % ring peak | "
                  "at bytes |", "|---|---|---|---|---|"]
        for kind, k in sorted(co["per_kind"].items()):
            pct = k.get("pct_of_ring_peak")
            lines.append(
                f"| {kind} | {k.get('fabric') or co.get('fabric') or '—'}"
                f" | {k.get('bus_gbps'):.2f} | "
                + (f"{pct:.1f}" if pct is not None else "—")
                + f" | {k.get('message_bytes')} |")
        lines.append("")
    sv = r.get("serving") or {}
    if sv.get("enabled"):
        lines += ["## Serving (latency SLOs)", "",
                  f"**serve_status: {sv['status']}** — "
                  + ", ".join(f"{rule} {st}"
                              for rule, st in sv["gates"].items()), "",
                  f"- {sv['completed']}/{sv['requests']} requests, "
                  f"{sv['generated_tokens']} tokens in "
                  f"{sv['wall_s']:.3f}s "
                  f"({sv['tokens_per_sec_per_chip']} tok/s/chip"
                  + (f", {sv['tokens_per_chip_ratio']}x baseline"
                     if sv.get("tokens_per_chip_ratio") is not None
                     else "") + ")",
                  f"- TTFT p50/p99: {sv['ttft_p50_s']}/"
                  f"{sv['ttft_p99_s']}s; ITL p50/p99: "
                  f"{sv['itl_p50_s']}/{sv['itl_p99_s']}s",
                  f"- {sv['slots']} slot(s), decode_k "
                  f"{sv['decode_k']}, kv pages of "
                  f"{sv['kv_page_tokens']} token(s), "
                  f"queue depth max {sv['queue_depth_max']}, compiles "
                  f"{sv['prefill_compiles']} prefill / "
                  f"{sv['decode_compiles']} decode", ""]
        if sv.get("kv_pages_used_mean") is not None:
            lines += [f"- kv pages read a dispatch: "
                      f"{sv['kv_pages_used_mean']} of "
                      f"{sv['kv_pages_total']} pool pages mapped by the "
                      f"slots' rows (mean over decode dispatches, peak "
                      f"{sv['kv_pages_used_peak']}): a page-bounded "
                      f"decode read copies these a layer a token step, "
                      f"the masked read the whole pool", ""]
        if sv.get("block_length"):
            # a run record from before the fused commit has neither count
            fused = (f"; {sv['commits_fused']} commit(s) fused into a next "
                     f"block's first step, {sv['forwards_launched']} "
                     f"forward(s) launched"
                     if sv.get("forwards_launched") is not None else "")
            lines += [f"- generation by blocks of {sv['block_length']}: "
                      f"{sv['forwards_per_token']} forward(s) a token "
                      f"emitted (a block takes its denoising steps, and "
                      f"its commit rides in the slot's next dispatch"
                      f"{fused}), {sv['tokens_per_dispatch']} token(s) a "
                      f"dispatch over all slots", ""]
        if sv.get("moe_pairs_per_expert_mean") is not None:
            lines += [f"- experts held here: "
                      f"{sv['moe_pairs_per_expert_mean']} pair(s) an "
                      f"expert a layer a token step, "
                      f"{sv['moe_experts_hit_mean']} expert(s) hit a "
                      f"layer a step"
                      + (f" in {sv['moe_blocks_mean']} block(s) of rows"
                         if sv.get("moe_blocks_mean") is not None else "")
                      + (f", experts_path {sv['experts_path']}"
                         if sv.get("experts_path") else "")
                      + f" (means over decode dispatches); "
                      f"kv state at peak: {sv['kv_pages_used_peak']}/"
                      f"{sv['kv_pages_total']} full-layer pages"
                      + (f", {sv['kv_window_tokens_peak']}/"
                         f"{sv['kv_window_tokens_total']} window tokens a "
                         f"window layer"
                         if sv.get("kv_window_tokens_total") else ""), ""]
        if sv.get("moe_zero_share") is not None:
            lines += [f"- identity experts: "
                      f"{sv['moe_pairs_zero_mean']} of "
                      f"{sv['moe_pairs_all_mean']} pair(s) routed a layer "
                      f"a token step go to an expert that returns its "
                      f"input ({100 * sv['moe_zero_share']:.1f} % of the "
                      f"routed work, done without a matrix)", ""]
        if sv.get("weights_resident"):
            wr = sv["weights_resident"]
            lines += [f"- weights at rest: {wr['leaves_cast']} of "
                      f"{wr['leaves']} leaves converted to the engine's "
                      f"dtype on the device, "
                      f"{wr['bytes_in'] / 1e9:.3f} GB -> "
                      f"{wr['bytes_out'] / 1e9:.3f} GB in "
                      f"{wr['seconds']:.3f}s ({wr['trees']} tree(s))", ""]
        if sv.get("arrived") is not None:
            lines += [f"- admission: {sv['arrived']} arrived = "
                      f"{sv['admitted']} admitted + "
                      f"{sv['shed_at_admission']} shed + "
                      f"{sv['expired_in_queue']} expired + "
                      f"{sv['rejected']} rejected "
                      f"(shed fraction {sv['shed_fraction']}"
                      + (f", queue cap {sv['queue_cap']}"
                         if sv.get("queue_cap") else "")
                      + (f", deadline {sv['ttft_deadline_s']}s"
                         if sv.get("ttft_deadline_s") else "") + ")",
                      ""]
        if sv.get("adapt_transitions"):
            lines += ["- degradation: " + "; ".join(
                f"L{t['from_level']}→L{t['to_level']} "
                f"(decode_k {t['decode_k']}) at {t['t_s']}s"
                for t in sv["adapt_transitions"]), ""]
        if sv.get("tuning"):
            t = sv["tuning"]
            lines += [f"- serve tune: {t.get('status')} "
                      f"({t.get('source')}, {t.get('trials')} trial(s)) "
                      f"→ decode_k {t.get('decode_k')}, kv page "
                      f"tokens {t.get('kv_page_tokens')}, speculate_k "
                      f"{t.get('speculate_k')}", ""]
    fl = r.get("flights") or {}
    if fl.get("enabled"):
        cn = fl.get("counts") or {}
        worst = fl.get("ttft_decomp_worst_s")
        lines += ["## Request flights", "",
                  "**ledger "
                  + ("exact" if fl.get("exact") else "**INEXACT**")
                  + f"** — {fl.get('flights')} flight(s): "
                  f"{cn.get('completed')} completed, "
                  f"{cn.get('evicted')} evicted, "
                  f"{cn.get('shed_at_admission')} shed, "
                  f"{cn.get('expired_in_queue')} expired, "
                  f"{cn.get('rejected')} rejected, "
                  f"{cn.get('lost')} lost"
                  + (" · partition reconciled"
                     if fl.get("partition_checked") else "")
                  + (" · trace cross-checked"
                     if fl.get("trace_checked") else ""), "",
                  f"- TTFT decomposition "
                  f"{fl.get('ttft_decomp_status')}: worst "
                  f"|ttft − (queue + prefill)| = "
                  + (f"{worst * 1e6:.2f}µs" if worst is not None
                     else "—")
                  + f" over {fl.get('decomposed')} flight(s) "
                  f"(tol {fl.get('ttft_decomp_tol_s')}s)", ""]
        dc = fl.get("decomposition") or {}
        if any((dc.get(k) or {}).get("n") for k in dc):
            lines += ["| component | n | p50 s | p99 s |",
                      "|---|---|---|---|"]
            for comp in ("queue_wait", "prefill", "ttft", "decode",
                         "e2e"):
                d = dc.get(comp) or {}
                if d.get("n"):
                    lines.append(f"| {comp} | {d['n']} | "
                                 f"{d.get('p50_s')} | "
                                 f"{d.get('p99_s')} |")
            lines.append("")
        spec_traj = fl.get("spec_accept_over_time") or []
        if spec_traj:
            first, last = spec_traj[0], spec_traj[-1]
            lines += [f"- spec accept trajectory: "
                      f"{first.get('spec_accept_rate')} @ "
                      f"{first.get('t_s')}s → "
                      f"{last.get('spec_accept_rate')} @ "
                      f"{last.get('t_s')}s "
                      f"({len(spec_traj)} tick(s))", ""]
        tl = fl.get("shed_timeline") or []
        if tl:
            shown = tl[:10]
            lines += ["- shed/evict timeline: " + "; ".join(
                f"{e.get('event')} rid={e.get('rid')} @ "
                f"{e.get('t_s')}s" for e in shown)
                + (f" … ({len(tl)} total)"
                   if len(tl) > len(shown) else ""), ""]
        for p in fl.get("problems") or []:
            lines.append(f"- ⚠️ {p}")
        if fl.get("problems"):
            lines.append("")
    gp = r.get("goodput") or {}
    if gp.get("enabled"):
        frac = gp.get("fraction")
        scope = ("across attempts" if gp.get("cross_attempt")
                 else "this attempt (run-end estimate)")
        lines += ["## Goodput (wall-clock accounting)", "",
                  f"**goodput_status: {gp['status']}** — "
                  + (f"{100 * frac:.1f}%" if frac is not None else "—")
                  + f" of {gp.get('total_wall_s') or 0:.2f}s wall was "
                    f"productive step time {scope} (floor "
                    f"{100 * gp['min_fraction']:.0f}%)"]
        if gp.get("cross_attempt"):
            lines += [f"- partition "
                      + ("exact" if gp.get("exact") else "**INEXACT**")
                      + f" (±{100 * (gp.get('tolerance') or 0):.0f}% "
                        f"pinned), {gp.get('lost_steps')} step(s) lost "
                        f"to preemption"]
        bk = gp.get("buckets") or {}
        if bk:
            lines.append("- buckets: " + ", ".join(
                f"{k} {v:.2f}s" for k, v in bk.items()
                if isinstance(v, (int, float))))
        lines.append("")
        atts = gp.get("attempts") or []
        if gp.get("cross_attempt") and atts:
            lines += ["| attempt | wall s | rc | verdict | steps | "
                      "lost | productive s | residue s |",
                      "|---|---|---|---|---|---|---|---|"]
            for a in atts:
                ab = a.get("buckets") or {}
                lines.append(
                    f"| {a.get('attempt')} | "
                    f"{a.get('wall_s') or 0:.2f} | {a.get('rc')} | "
                    f"{a.get('verdict') or '—'} | "
                    f"{a.get('steps_done') if a.get('steps_done') is not None else '—'} | "
                    f"{a.get('lost_steps') if a.get('lost_steps') is not None else '—'} | "
                    f"{ab.get('productive', 0.0):.2f} | "
                    f"{ab.get('residue', 0.0):.2f} |")
            lines.append("")
        for p in gp.get("problems") or []:
            lines.append(f"- ⚠️ {p}")
        if gp.get("problems"):
            lines.append("")
    mem = r.get("memory") or {}
    if mem.get("enabled"):
        frac = mem.get("headroom_fraction")
        total = mem.get("total_hbm_bytes") or 0
        lines += ["## Memory (per-device HBM ledger)", "",
                  f"**hbm_headroom_status: {mem['status']}** — "
                  + (f"{100 * frac:.1f}%" if frac is not None else "—")
                  + f" of {total / 2**20:.0f} MiB device HBM "
                    f"unattributed ({mem.get('mode')} lane, floor "
                    f"{100 * (mem.get('min_fraction') or 0):.0f}%)"
                  + f" · partition "
                  + ("exact" if mem.get("exact") else "**INEXACT**"), ""]
        deltas = mem.get("bucket_delta_bytes") or {}
        has_delta = bool(deltas)
        lines += ["| bucket | MiB | % of HBM |"
                  + (" Δ vs baseline MiB |" if has_delta else ""),
                  "|---|---|---|" + ("---|" if has_delta else "")]
        for b in memledger_mod.BUCKETS:
            v = (mem.get("buckets") or {}).get(b)
            row = (f"| {b} | "
                   + (f"{v / 2**20:.1f}" if v is not None else "—")
                   + " | "
                   + (f"{100 * v / total:.1f}"
                      if v is not None and total else "—") + " |")
            if has_delta:
                d = deltas.get(b)
                row += (f" {d / 2**20:+.1f} |" if d is not None
                        else " — |")
            lines.append(row)
        lines.append("")
        if mem.get("watermark_bytes") is not None:
            lines += [f"- measured watermark: "
                      f"{mem['watermark_bytes'] / 2**20:.1f} MiB "
                      f"({mem.get('watermark_source')})"]
        if mem.get("programs"):
            lines += ["- programs: " + ", ".join(mem["programs"])
                      + ("" if mem.get("program_temp_complete")
                         else " (some without memory_analysis — "
                              "program_temp under-counts)")]
        for p in mem.get("problems") or []:
            lines.append(f"- ⚠️ {p}")
        for n in mem.get("notes") or []:
            lines.append(f"- {n}")
        lines.append("")
    al = r.get("alerts") or {}
    if al.get("enabled"):
        lines += ["## Alerts (live telemetry)", ""]
        if al["history"]:
            lines += ["| rule | host | first fired | duration | state "
                      "| value vs threshold |",
                      "|---|---|---|---|---|---|"]
            for a in al["history"]:
                host = a["host"] if a.get("host") is not None else "pod"
                first = (f"step {a['first_step']}"
                         if a.get("first_step") is not None else "—")
                val = (f"{a['value']:.4g} vs {a['threshold']:.4g}"
                       if isinstance(a.get("value"), (int, float))
                       and isinstance(a.get("threshold"), (int, float))
                       else "—")
                lines.append(
                    f"| {a['alert']} | {host} | {first} | "
                    f"{a.get('duration_s', 0):.1f}s | {a.get('state')} "
                    f"| {val} |")
            lines.append("")
        else:
            lines += ["- no alerts fired", ""]
        for w in al.get("warnings", []):
            lines.append(f"- ⚠️ {w}")
        if al.get("warnings"):
            lines.append("")
    if r["stragglers"]["attribution"]:
        lines += ["## Straggler attribution", ""]
        for a in r["stragglers"]["attribution"]:
            lines.append(
                f"- host{a['process']}: **{a['phase']}** self time "
                f"{a['self_s']:.3f}s vs pod median "
                f"{a['pod_median_s']:.3f}s (+{a['excess_s']:.3f}s)")
        lines.append("")
    tr = r["trace"]
    lines += [f"_trace: {tr['spans']} spans from {tr['hosts']} host(s), "
              f"{tr['dropped']} dropped_", ""]
    return "\n".join(lines)


# -------------------------------------------------------------- CLI


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpudist.obs.report",
        description="offline tpudist run report from metrics.jsonl + "
                    "pod_trace.json")
    p.add_argument("--run-dir", type=str, default=None,
                   help="directory holding metrics.jsonl and "
                        "pod_trace.json (a train run's --save-dir)")
    p.add_argument("--metrics", type=str, default=None,
                   help="explicit metrics.jsonl path")
    p.add_argument("--trace", type=str, default=None,
                   help="explicit pod_trace.json (or trace.worker<i>."
                        "json) path")
    p.add_argument("--baseline", type=str, default=None,
                   help="baseline JSON carrying steps_per_sec (e.g. a "
                        "prior run_report.json) for the regression gate "
                        "— a prior report also baselines the exposed-"
                        "comm fraction for the devtime delta")
    p.add_argument("--collectives", type=str, default=None,
                   help="BENCH_COLLECTIVES.json (python -m "
                        "tpudist.bench.sweep --bench-out) folded into "
                        "the report's "
                        "Collectives section (default: <run-dir>/"
                        "BENCH_COLLECTIVES.json when present)")
    p.add_argument("--alerts", type=str, default=None,
                   help="alert history for the Alerts section: "
                        "alerts.jsonl (the live aggregator's transition "
                        "log) or a live_status.json (default: <run-dir>/"
                        "alerts.jsonl, else <run-dir>/live_status.json "
                        "when present)")
    p.add_argument("--goodput", type=str, default=None,
                   help="prebuilt goodput ledger JSON (python -m "
                        "tpudist.obs.goodput) for the Goodput section "
                        "(default: <run-dir>/goodput.json when "
                        "present)")
    p.add_argument("--attempts", type=str, default=None,
                   help="attempts.jsonl (launcher-written, one record "
                        "per requeue attempt): when present — or found "
                        "in <run-dir> — the cross-attempt goodput "
                        "ledger is built here and folded into the "
                        "Goodput section")
    p.add_argument("--memledger", type=str, default=None,
                   help="memledger.json (the train/serve CLIs write it, "
                        "python -m tpudist.obs.memledger rebuilds it) "
                        "for the Memory section (default: <run-dir>/"
                        "memledger.json when present; a kind=memledger "
                        "record is the in-stream fallback)")
    p.add_argument("--regress-min", type=float, default=None,
                   help=f"regression floor as a fraction of baseline "
                        f"steps/s (default $TPUDIST_REGRESS_MIN, else "
                        f"{REGRESS_MIN_FRACTION})")
    p.add_argument("--out-json", type=str, default=None,
                   help="run_report.json path (default: <run-dir>/"
                        "run_report.json)")
    p.add_argument("--out-md", type=str, default=None,
                   help="run_report.md path (default: <run-dir>/"
                        "run_report.md)")
    args = p.parse_args(argv)

    run_dir = args.run_dir or "."
    metrics_path = args.metrics or os.path.join(run_dir, "metrics.jsonl")
    trace_path = args.trace
    if trace_path is None:
        trace_path = os.path.join(run_dir, "pod_trace.json")
        if not os.path.exists(trace_path):
            # single-worker fallback: the local export is the pod trace
            alt = os.path.join(run_dir, "trace.worker0.json")
            if os.path.exists(alt):
                trace_path = alt
    for path, what in ((metrics_path, "metrics"), (trace_path, "trace")):
        if not os.path.exists(path):
            print(f"tpudist.obs.report: missing {what} file {path}",
                  file=sys.stderr)
            return 2

    metrics = load_metrics(metrics_path)
    trace_doc = load_trace(trace_path)
    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        warn_newer_schema(baseline, "baseline")
    collectives = None
    coll_path = args.collectives or os.path.join(run_dir,
                                                 "BENCH_COLLECTIVES.json")
    if os.path.exists(coll_path):
        with open(coll_path) as f:
            collectives = json.load(f)
    elif args.collectives:
        print(f"tpudist.obs.report: missing collectives file "
              f"{coll_path}", file=sys.stderr)
        return 2

    alert_history = None
    alerts_path = args.alerts
    if alerts_path is None:
        for cand in (os.path.join(run_dir, "alerts.jsonl"),
                     os.path.join(run_dir, "live_status.json")):
            if os.path.exists(cand):
                alerts_path = cand
                break
    if alerts_path:
        if not os.path.exists(alerts_path):
            print(f"tpudist.obs.report: missing alerts file "
                  f"{alerts_path}", file=sys.stderr)
            return 2
        with open(alerts_path) as f:
            if alerts_path.endswith(".jsonl"):
                alert_history = [json.loads(line)
                                 for line in f if line.strip()]
            else:
                # a live_status.json: the final snapshot's full history
                status_doc = json.load(f)
                warn_newer_schema(status_doc, "alerts")
                alert_history = (status_doc.get("alerts") or {}).get(
                    "history", [])

    # the goodput ledger: a prebuilt goodput.json wins; else an
    # attempts.jsonl (given or discovered in the run dir) builds the
    # cross-attempt ledger right here (goodput is jax-free like this
    # whole CLI); single-attempt runs fall back to the kind=goodput
    # record inside build_report
    goodput_doc = None
    gp_path = args.goodput or os.path.join(run_dir, "goodput.json")
    if args.goodput and not os.path.exists(gp_path):
        print(f"tpudist.obs.report: missing goodput file {gp_path}",
              file=sys.stderr)
        return 2
    if os.path.exists(gp_path):
        with open(gp_path) as f:
            goodput_doc = json.load(f)
        warn_newer_schema(goodput_doc, "goodput")
    else:
        attempts_path = args.attempts or os.path.join(
            run_dir, goodput_mod.ATTEMPTS_NAME)
        if args.attempts and not os.path.exists(attempts_path):
            print(f"tpudist.obs.report: missing attempts file "
                  f"{attempts_path}", file=sys.stderr)
            return 2
        if os.path.exists(attempts_path):
            goodput_doc = goodput_mod.build_from_dir(
                run_dir, attempts_path=attempts_path)

    # the memory ledger: an explicit --memledger must exist; the
    # discovered <run-dir>/memledger.json is optional — run dirs from
    # before the ledger still fold (the section reads UNGATEABLE)
    memledger_doc = None
    ml_path = args.memledger or os.path.join(run_dir,
                                             memledger_mod.LEDGER_NAME)
    if args.memledger and not os.path.exists(ml_path):
        print(f"tpudist.obs.report: missing memledger file {ml_path}",
              file=sys.stderr)
        return 2
    if os.path.exists(ml_path):
        with open(ml_path) as f:
            memledger_doc = json.load(f)
        warn_newer_schema(memledger_doc, "memledger")

    report = build_report(metrics, trace_doc, baseline=baseline,
                          regress_min=args.regress_min,
                          collectives=collectives,
                          alert_history=alert_history,
                          goodput=goodput_doc,
                          memledger=memledger_doc)
    out_json = args.out_json or os.path.join(run_dir, "run_report.json")
    out_md = args.out_md or os.path.join(run_dir, "run_report.md")
    for path, payload in ((out_json, json.dumps(report, indent=1)),
                          (out_md, to_markdown(report))):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)
    print(f"tpudist: run report {report['verdict']}: {out_json} "
          f"({report['trace']['spans']} spans, "
          f"{len(report['hosts'])} host(s))")
    return 0 if report["verdict"] != FAIL else 1


if __name__ == "__main__":
    sys.exit(main())
