"""Goodput ledger: cross-attempt wall-clock accounting for preempted runs.

The framework *survives* preemption (tpudist.elastic's requeue loop) and
*observes* single attempts in depth (flight recorder, tracer, devtime,
live bus) — but nothing answered the question an operator of
preemptible capacity actually asks: of the total wall-clock this run
consumed across ALL its requeue attempts, what fraction was productive
training?  This module is that answer: a ledger that ingests every
attempt of one ``run_id`` and partitions the run's total wall into
mutually exclusive buckets:

  * ``productive``       — steady-state step time that survived (kept
    steps; the goodput numerator);
  * ``compile``          — attempt 0's trace+compile warmup
    (``compile_warmup_s``);
  * ``rewarmup``         — the SAME cost paid AGAIN by requeued
    attempts (re-trace/re-compile after resume);
  * ``staging_exposed``  — H2D waits the staging pipeline failed to
    hide (``stage_wait_s``; they sit inside the timed windows, so they
    are carved OUT of productive);
  * ``ckpt``             — checkpoint enqueue cost on the step path
    plus drain stalls at wait/close;
  * ``eval``             — per-epoch held-out eval forwards;
  * ``lost``             — step time a kill threw away: steps computed
    AFTER the last committed checkpoint of a killed attempt, recovered
    from the dead attempt's heartbeat beacon vs the next attempt's
    ``kind=resume`` record;
  * ``startup``          — process spawn + imports + distributed/model
    init, from the attempt's launcher start stamp to its first metrics
    record;
  * ``off_pod``          — time with NO attempt running at all: requeue
    backoff + re-provisioning, from consecutive ``attempts.jsonl``
    deltas;
  * ``residue``          — the honest remainder (what a dead attempt
    never got to report, run-end export/verdict tails).

The partition is EXACT by the same discipline as the devtime
decomposition (PR 6): every attempt's buckets sum to that attempt's
wall because ``residue`` is defined as the remainder — and the ledger
FLAGS (``exact=False``) any attempt whose *measured* buckets exceed its
wall by more than the pinned :data:`TOLERANCE` (double counting), any
overlapping attempt stamps, and any global drift.  Dead attempts are
accounted from what actually survived the kill: the flushed
step/ckpt records (rate + progress), the final heartbeat beacon
(how far training really got), and the resuming attempt's ``kind=
resume`` record (what was committed) — everything unmeasurable lands
in ``residue``, never in a guessed bucket.

Inputs (all of them artifacts the framework already writes):

  * ``attempts.jsonl`` — NEW, launcher-written (launch_tpu.sh appends
    one record per workload invocation: attempt index, start/end
    epoch-seconds, rc, the requeue policy's verdict); also written by
    the scripted drill below;
  * ``metrics.jsonl``  — every record carries ``requeue_attempt``
    (stamped since the live-telemetry PR), so one file holds all
    attempts and splits cleanly;
  * heartbeat beacons  — ``heartbeat.worker<i>`` (current attempt) and
    ``heartbeat.worker<i>.attempt<K>`` (archived by the NEXT attempt's
    flight recorder — obs.heartbeat), the dead attempts' last progress
    counters;
  * ``alerts.jsonl`` / ``kind=resume`` records ride along in the same
    metrics stream.

jax-free by design (the offline-tooling contract shared with
:mod:`tpudist.obs.report`): the CLI runs on the CI host or a laptop
against scp'd artifacts.  The scripted ``--drill`` runs the real train
CLI in subprocesses (kill → requeue-policy → resume), writes
``attempts.jsonl`` exactly as the launcher would, and produces
``BENCH_GOODPUT.json`` — the acceptance artifact CI uploads.

CLI::

    python -m tpudist.obs.goodput --run-dir DIR \
        [--bench-out BENCH_GOODPUT.json] [--prom-out goodput.prom]
    python -m tpudist.obs.goodput --drill --run-dir DIR ...
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tpudist import rules as rules_lib

GOODPUT_SCHEMA_VERSION = 1
ATTEMPTS_NAME = "attempts.jsonl"
LEDGER_NAME = "goodput.json"

# Partition exactness: the pinned tolerance (fraction of the wall being
# partitioned) past which the ledger flags itself inexact — the same
# ±1% discipline the devtime decomposition pins (compute + exposed_comm
# + idle == window).
TOLERANCE = 0.01

SUCCESS = "success"     # mirrors tpudist.verdict vocabulary without the
FAIL = "fail"           # import (same pattern as obs.alerts)
UNGATEABLE = "ungateable"

# The goodput floor lives in tpudist.rules with every other gate
# (TPUDIST_GOODPUT_MIN, resolved at call time); the alias is this
# module's documented surface, like verdict's.
GOODPUT_MIN = rules_lib.GOODPUT_MIN

# Cross-attempt bucket names, display order. Per-attempt rows carry all
# but ``off_pod`` (time between attempts belongs to no attempt).
BUCKETS = ("productive", "compile", "rewarmup", "staging_exposed",
           "ckpt", "eval", "lost", "startup", "off_pod", "residue")
ATTEMPT_BUCKETS = tuple(b for b in BUCKETS if b != "off_pod")


def goodput_status(fraction: Optional[float],
                   min_fraction: Optional[float] = None) -> str:
    """Three-valued goodput verdict: UNGATEABLE with nothing measured
    (an empty ledger must not read as a goodput pass), else
    SUCCESS/FAIL by whether the productive fraction clears
    ``TPUDIST_GOODPUT_MIN``. Advisory, like the comm/staging gates — a
    run that completed with bad goodput is a capacity-efficiency
    finding, not a correctness failure."""
    if fraction is None:
        return UNGATEABLE
    if min_fraction is None:
        min_fraction = rules_lib.resolve("goodput")
    return SUCCESS if fraction >= min_fraction else FAIL


# ------------------------------------------------------------- ingestion


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue    # a torn tail line is not evidence
            if isinstance(rec, dict):
                out.append(rec)
    return out


def load_attempts(path: str) -> List[Dict[str, Any]]:
    """The launcher's per-invocation records, sorted by attempt index.
    Only parseable records with both timestamps count — the ledger's
    spine must be well-formed or absent, never guessed."""
    recs = [r for r in load_jsonl(path)
            if isinstance(r.get("start_ts"), (int, float))
            and isinstance(r.get("end_ts"), (int, float))]
    return sorted(recs, key=lambda r: int(r.get("attempt", 0)))


def find_metrics(run_dir: str) -> List[str]:
    """Every metrics.jsonl under the run directory: the top-level one
    (records self-identify by ``requeue_attempt``, so one appended file
    holds every attempt) plus per-attempt collection subdirs
    (``attempt<N>/metrics.jsonl``, the launcher's failure-path
    layout)."""
    paths = set(glob.glob(os.path.join(run_dir, "metrics.jsonl")))
    paths |= set(glob.glob(os.path.join(run_dir, "*", "metrics.jsonl")))
    return sorted(paths)


def find_beacons(run_dir: str) -> Dict[int, Dict[int, Dict[str, Any]]]:
    """``{attempt: {worker: beacon payload}}`` from every heartbeat
    file under the run dir (recursively — collection may nest
    per-attempt subdirs). The attempt comes from the payload's own
    ``requeue_attempt`` stamp (the archived ``.attempt<K>`` filename
    suffix is a fallback for beacons too old to carry it); duplicate
    (attempt, worker) pairs keep the furthest-progressed payload."""
    out: Dict[int, Dict[int, Dict[str, Any]]] = {}
    pattern = os.path.join(run_dir, "**", "heartbeat.worker*")
    for path in sorted(set(glob.glob(pattern, recursive=True))):
        tail = os.path.basename(path).rsplit(".worker", 1)[-1]
        suffix_attempt = None
        if "." in tail:
            tail, _, suffix = tail.partition(".")
            if suffix.startswith("attempt") and suffix[7:].isdigit():
                suffix_attempt = int(suffix[7:])
            else:
                continue        # .tmp or foreign suffix: not a beacon
        if not tail.isdigit():
            continue
        worker = int(tail)
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            continue
        att = payload.get("requeue_attempt")
        att = int(att) if isinstance(att, (int, float)) else suffix_attempt
        if att is None:
            att = 0
        cur = out.setdefault(att, {}).get(worker)
        if cur is None or _progress_key(payload) > _progress_key(cur):
            out[att][worker] = payload
    return out


def _progress_key(payload: Dict[str, Any]) -> Tuple[int, int]:
    """Beacon ordering: (epoch, step_in_epoch) LEXICOGRAPHIC — a stale
    epoch-0/step-7 beacon must never beat a fresher epoch-1/step-2 one
    (step resets every epoch)."""
    epoch = payload.get("epoch")
    step = payload.get("step")
    return (int(epoch) if isinstance(epoch, (int, float)) else -1,
            int(step) if isinstance(step, (int, float)) else -1)


# ------------------------------------------------- per-attempt buckets


def _kind(recs: List[Dict[str, Any]], kind: str) -> List[Dict[str, Any]]:
    return [r for r in recs if r.get("kind") == kind]


def _ckpt_seconds(recs: List[Dict[str, Any]]) -> float:
    """Checkpoint cost: per-save enqueue (what the step path paid,
    ``kind=ckpt``) plus the run-total drain stall (``kind=ckpt_drain``
    — the honest enqueue/drain split from the checkpointing work)."""
    s = sum(float(r.get("enqueue_ms") or 0.0) for r in _kind(recs, "ckpt"))
    drains = _kind(recs, "ckpt_drain")
    if drains:
        s += float(drains[-1].get("drain_ms") or 0.0)
    return s / 1e3


def _eval_seconds(recs: List[Dict[str, Any]]) -> float:
    return sum(float(r.get("eval_s") or 0.0) for r in _kind(recs, "epoch"))


def attempt_record(history: Sequence[Dict[str, Any]], *,
                   wall_s: float, requeue_attempt: int = 0
                   ) -> Optional[Dict[str, Any]]:
    """The ATTEMPT-local goodput estimate the train loop logs at run
    end (``kind=goodput``): the same bucket math the cross-attempt
    ledger applies to a completed attempt, over this process's own
    record history and wall clock. The live aggregator observes its
    ``fraction`` against the goodput rule, so a badput-heavy run alerts
    mid-fleet — the offline ledger then refines it with startup/off-pod
    time only the launcher can see. None when nothing was measured."""
    timings = [r for r in history if r.get("kind") == "timing"]
    if not timings or wall_s <= 0:
        return None
    t = timings[-1]
    warm = float(t.get("compile_warmup_s") or 0.0)
    wait = float(t.get("stage_wait_s") or 0.0)
    productive = max(0.0, float(t.get("run_s") or 0.0) - wait)
    buckets = {
        ("compile" if requeue_attempt == 0 else "rewarmup"): warm,
        "staging_exposed": wait,
        "productive": productive,
        "ckpt": _ckpt_seconds(list(history)),
        "eval": _eval_seconds(list(history)),
    }
    frac = round(productive / wall_s, 6)
    return {"fraction": frac, "status": goodput_status(frac),
            "wall_s": round(wall_s, 6),
            "requeue_attempt": requeue_attempt,
            **{f"{k}_s": round(v, 6) for k, v in buckets.items()}}


def _completed_into(buckets: Dict[str, float], recs, timing,
                    first_attempt: bool) -> Dict[str, Any]:
    warm = float(timing.get("compile_warmup_s") or 0.0)
    buckets["compile" if first_attempt else "rewarmup"] += warm
    wait = float(timing.get("stage_wait_s") or 0.0)
    buckets["staging_exposed"] += wait
    run_s = float(timing.get("run_s") or 0.0)
    buckets["productive"] += max(0.0, run_s - wait)
    steps = timing.get("steps")
    sps = (steps / run_s) if steps and run_s > 0 else None
    return {"steps_done": steps, "lost_steps": 0,
            "steps_per_sec": round(sps, 4) if sps else None}


def _beacon_progress(beacons: Optional[Dict[int, Dict[str, Any]]]
                     ) -> Tuple[Optional[int], Optional[int]]:
    """(step_in_epoch, epoch) of the furthest-progressed worker beacon
    for one attempt — how far the attempt REALLY got before dying.
    Ordered by (epoch, step): step resets per epoch, so a straggler's
    epoch-0/step-7 beacon must not outrank a peer's epoch-1/step-2."""
    best = None
    for payload in (beacons or {}).values():
        step = payload.get("step")
        if not isinstance(step, (int, float)) or step < 0:
            continue
        if best is None or _progress_key(payload) > _progress_key(best):
            best = payload
    if best is None:
        return None, None
    return int(best["step"]), best.get("epoch")


def _dead_into(buckets: Dict[str, float], recs, *, first_ts,
               next_resume, beacons, first_attempt: bool,
               wall: float = float("inf")) -> Dict[str, Any]:
    """Bucket a KILLED attempt from what survived: flushed step/ckpt
    records give the rate and committed progress, the final beacon the
    true progress, the resuming attempt's record what was kept.
    Unmeasurable remainder (the kill's whole point) stays residue."""
    steps = _kind(recs, "step")
    ckpts = _kind(recs, "ckpt")
    resumes = [r for r in _kind(recs, "resume")
               if r.get("status") == SUCCESS]
    sps = None
    for r in reversed(steps):
        v = r.get("steps_per_sec")
        if isinstance(v, (int, float)) and v > 0:
            sps = float(v)
            break
    g0 = int(resumes[-1].get("resumed_from_step") or 0) if resumes else 0
    b_step, b_epoch = _beacon_progress(beacons)
    # final global step: the last flushed record's global step, extended
    # by the beacon's in-epoch progress when both sit in the same epoch
    g1 = None
    if ckpts:
        base = ckpts[-1]
        g1 = int(base.get("step") or 0)
        if b_step is not None and b_epoch == base.get("epoch"):
            g1 += max(0, b_step - int(base.get("step_in_epoch") or 0))
    elif steps:
        g1 = int(steps[-1].get("step") or 0)
        if b_step is not None and b_epoch == 0 and g0 == 0:
            g1 = max(g1, b_step)    # fresh epoch-0 run: global == in-epoch
    elif b_step is not None and b_epoch == 0 and g0 == 0:
        g1 = b_step
    steps_done = max(0, g1 - g0) if g1 is not None else None

    # lost steps: the resuming attempt's own accounting first (it read
    # the SAME beacon at restore time), the beacon-vs-resume-point diff
    # as the independent cross-check the acceptance drill pins
    lost_beacon = None
    if next_resume is not None and b_step is not None \
            and next_resume.get("epoch") == b_epoch:
        lost_beacon = max(0, b_step - int(
            next_resume.get("step_in_epoch") or 0))
    if next_resume is not None and next_resume.get("status") == SUCCESS:
        lost = next_resume.get("steps_lost")
        lost = int(lost) if isinstance(lost, (int, float)) else lost_beacon
    else:
        # no successful restore: EVERYTHING this attempt computed was
        # thrown away (a fresh start redoes it all)
        lost = steps_done
    lost = int(lost or 0)
    if steps_done is not None:
        lost = min(lost, steps_done)
    if sps:
        buckets["lost"] += lost / sps
        kept = max(0, (steps_done if steps_done is not None else lost)
                   - lost)
        buckets["productive"] += kept / sps
        if steps and first_ts is not None:
            # compile estimate: the gap from the first metrics record to
            # the first logged step, minus the step time that interval
            # covered — the trace+compile cost a dead attempt's missing
            # timing record never reported
            t1 = steps[0].get("ts")
            n1 = max(0, int(steps[0].get("step") or 0) - g0)
            if isinstance(t1, (int, float)):
                est = (float(t1) - first_ts) - n1 / sps
                # the estimate is a timestamp inference, and inferring
                # MORE than the attempt's unaccounted wall is by
                # definition overcounting — clamp to the remaining
                # headroom, so estimator noise on a dead attempt cannot
                # flag the partition inexact (measured buckets keep
                # their own double-counting check)
                est = min(max(0.0, est),
                          max(0.0, wall - sum(buckets.values())))
                buckets["compile" if first_attempt
                        else "rewarmup"] += est
    return {"steps_done": steps_done, "lost_steps": lost,
            "lost_steps_beacon": lost_beacon,
            "beacon_step": b_step,
            "steps_per_sec": round(sps, 4) if sps else None}


# ------------------------------------------------------------ the ledger


def build_ledger(attempts: List[Dict[str, Any]],
                 records: List[Dict[str, Any]], *,
                 beacons: Optional[Dict[int, Dict[int, Dict]]] = None,
                 tolerance: float = TOLERANCE,
                 run_id: Optional[str] = None) -> Dict[str, Any]:
    """Partition the run's total wall-clock (first attempt start →
    last attempt end, from ``attempts.jsonl``) into the goodput
    buckets. The sum of all buckets equals the total EXACTLY by
    construction (residue is the remainder); ``exact`` certifies the
    measured buckets never exceeded any attempt's wall (no double
    counting) and the attempt stamps never overlapped, within the
    pinned tolerance."""
    attempts = [dict(a) for a in attempts]
    if not attempts:
        raise ValueError("no attempt records — attempts.jsonl is the "
                         "ledger's spine (the launcher and the drill "
                         "both write it)")
    if run_id is None:
        # the NEWEST stamped launch is the run being accounted: a retry
        # from the same artifacts dir appends a fresh run_id, and stale
        # runs' evidence must not fold into this ledger
        run_id = next((a.get("run_id") for a in reversed(attempts)
                       if a.get("run_id")), None) \
            or next((r.get("run_id") for r in reversed(records)
                     if r.get("run_id")), None)

    def _ours(rec: Dict[str, Any]) -> bool:
        # unstamped evidence stays (scripted/old artifacts); a DIFFERENT
        # run_id is another launch's leftovers
        rid = rec.get("run_id")
        return run_id is None or not rid or rid == run_id

    attempts = sorted((a for a in attempts if _ours(a)),
                      key=lambda a: int(a.get("attempt", 0)))
    if not attempts:
        raise ValueError(f"no attempt records for run_id {run_id!r}")
    beacons = {att: {w: p for w, p in workers.items() if _ours(p)}
               for att, workers in (beacons or {}).items()}
    by_att: Dict[int, List[Dict[str, Any]]] = {}
    for r in records:
        if not _ours(r):
            continue
        a = r.get("requeue_attempt")
        by_att.setdefault(int(a) if isinstance(a, (int, float)) else 0,
                          []).append(r)
    for recs in by_att.values():
        recs.sort(key=lambda r: r.get("ts") or 0)

    t0 = float(attempts[0]["start_ts"])
    t1 = float(attempts[-1]["end_ts"])
    total_wall = max(0.0, t1 - t0)
    scale = max(total_wall, 1e-9)
    totals = {k: 0.0 for k in BUCKETS}
    rows: List[Dict[str, Any]] = []
    exact = True
    problems: List[str] = []
    prev_end: Optional[float] = None

    for i, a in enumerate(attempts):
        att = int(a.get("attempt", i))
        start, end = float(a["start_ts"]), float(a["end_ts"])
        wall = max(0.0, end - start)
        if prev_end is not None:
            gap = start - prev_end
            if gap < -tolerance * scale:
                exact = False
                problems.append(f"attempt {att} overlaps the previous "
                                f"attempt by {-gap:.3f}s")
            totals["off_pod"] += max(0.0, gap)
        prev_end = end

        recs = by_att.get(att, [])
        buckets = {k: 0.0 for k in ATTEMPT_BUCKETS}
        first_ts = None
        ts_vals = [float(r["ts"]) for r in recs
                   if isinstance(r.get("ts"), (int, float))]
        if ts_vals:
            first_ts = min(ts_vals)
            buckets["startup"] = min(max(0.0, first_ts - start), wall)
        timings = _kind(recs, "timing")
        next_resumes = [r for r in by_att.get(att + 1, [])
                        if r.get("kind") == "resume"]
        # measured ckpt/eval land FIRST so the dead-attempt estimator
        # sees the true remaining headroom when it clamps
        buckets["ckpt"] += _ckpt_seconds(recs)
        buckets["eval"] += _eval_seconds(recs)
        info: Dict[str, Any] = {}
        if timings:
            info = _completed_into(buckets, recs, timings[-1],
                                   first_attempt=(i == 0))
        else:
            info = _dead_into(
                buckets, recs, first_ts=first_ts,
                next_resume=next_resumes[-1] if next_resumes else None,
                beacons=beacons.get(att), first_attempt=(i == 0),
                wall=wall)
        measured = sum(v for k, v in buckets.items() if k != "residue")
        buckets["residue"] = wall - measured
        if buckets["residue"] < -tolerance * max(wall, 1e-9):
            exact = False
            problems.append(
                f"attempt {att}: measured buckets exceed its "
                f"{wall:.3f}s wall by {-buckets['residue']:.3f}s — "
                f"double counting")
        for k, v in buckets.items():
            totals[k] += v
        rows.append({
            "attempt": att, "start_ts": start, "end_ts": end,
            "wall_s": round(wall, 6), "rc": a.get("rc"),
            "verdict": a.get("verdict"), "records": len(recs),
            "buckets": {k: round(v, 6) for k, v in buckets.items()},
            **info})

    drift = abs(sum(totals.values()) - total_wall)
    if drift > tolerance * scale:
        exact = False
        problems.append(f"bucket sum drifts {drift:.3f}s from the "
                        f"{total_wall:.3f}s total wall")
    lost_steps = sum(int(r.get("lost_steps") or 0) for r in rows)
    frac = (round(totals["productive"] / total_wall, 6)
            if total_wall > 0 else None)
    return {
        "schema": GOODPUT_SCHEMA_VERSION,
        "run_id": run_id,
        "attempts": rows,
        "totals": {k: round(v, 6) for k, v in totals.items()},
        "total_wall_s": round(total_wall, 6),
        "goodput_fraction": frac,
        "goodput_status": goodput_status(frac),
        "goodput_min": rules_lib.resolve("goodput"),
        "lost_steps": lost_steps,
        "exact": exact,
        "tolerance": tolerance,
        "problems": problems,
    }


def build_from_dir(run_dir: str, *,
                   attempts_path: Optional[str] = None,
                   tolerance: float = TOLERANCE
                   ) -> Optional[Dict[str, Any]]:
    """Discover a run directory's artifacts (attempts.jsonl, every
    metrics.jsonl, all beacon generations) and build the ledger; None
    when there is no attempts.jsonl to anchor wall-clock to."""
    path = attempts_path or os.path.join(run_dir, ATTEMPTS_NAME)
    if not os.path.exists(path):
        return None
    attempts = load_attempts(path)
    if not attempts:
        return None
    records: List[Dict[str, Any]] = []
    for mp in find_metrics(run_dir):
        records.extend(load_jsonl(mp))
    return build_ledger(attempts, records, beacons=find_beacons(run_dir),
                        tolerance=tolerance)


# --------------------------------------------------- prometheus textfile


_PROM_HELP = {
    "tpudist_goodput_info": "Ledger identity (labels carry run_id and "
                            "attempt count).",
    "tpudist_goodput_fraction": "Productive training fraction of the "
                                "cross-attempt wall clock.",
    "tpudist_goodput_total_wall_seconds": "Total wall from first "
                                          "attempt start to last "
                                          "attempt end.",
    "tpudist_goodput_bucket_seconds": "Wall seconds per badput bucket "
                                      "(the partition sums to total).",
    "tpudist_goodput_lost_steps": "Steps recomputed after preemption "
                                  "kills (beacon vs resume point).",
    "tpudist_goodput_exact": "1 when the partition met the pinned "
                             "tolerance.",
}


def prometheus_text(ledger: Dict[str, Any]) -> str:
    """The ledger as Prometheus text exposition (0.0.4) — the textfile-
    collector shape for CI/dashboards, rendered with the SAME escaping
    and number formatting as the live exporter so the two tpudist_*
    families read identically. Pure function, golden-tested; the value
    of ``tpudist_goodput_fraction`` is byte-identical to the ledger's
    (the consumer-parity pin)."""
    from tpudist.obs.live import _prom_escape, _prom_num
    out: List[str] = []

    def metric(name, samples, mtype="gauge"):
        rows = [(lbl, v) for lbl, v in samples if v is not None]
        if not rows:
            return
        out.append(f"# HELP {name} {_PROM_HELP[name]}")
        out.append(f"# TYPE {name} {mtype}")
        for lbl, v in rows:
            label_s = ",".join(f'{k}="{_prom_escape(x)}"'
                               for k, x in lbl.items())
            out.append(f"{name}{{{label_s}}} {_prom_num(v)}"
                       if label_s else f"{name} {_prom_num(v)}")

    metric("tpudist_goodput_info",
           [({"run_id": ledger.get("run_id") or "",
              "attempts": str(len(ledger.get("attempts", [])))}, 1)])
    metric("tpudist_goodput_fraction",
           [({}, ledger.get("goodput_fraction"))])
    metric("tpudist_goodput_total_wall_seconds",
           [({}, ledger.get("total_wall_s"))])
    metric("tpudist_goodput_bucket_seconds",
           [({"bucket": k}, (ledger.get("totals") or {}).get(k))
            for k in BUCKETS])
    metric("tpudist_goodput_lost_steps",
           [({}, ledger.get("lost_steps"))])
    metric("tpudist_goodput_exact",
           [({}, 1 if ledger.get("exact") else 0)])
    return "\n".join(out) + "\n"


def bench_artifact(ledger: Dict[str, Any]) -> Dict[str, Any]:
    """BENCH_GOODPUT.json on the shared BENCH_* harness shape: the
    headline value is the goodput fraction, the detail is the full
    ledger."""
    return {
        "metric": "goodput_fraction",
        "value": ledger.get("goodput_fraction"),
        "unit": "productive wall / total wall across requeue attempts",
        "detail": ledger,
    }


def append_attempt(path: str, *, attempt: int, start_ts: float,
                   end_ts: float, rc: int, verdict: str,
                   run_id: Optional[str] = None,
                   mode: str = "train") -> None:
    """One attempts.jsonl record — the same shape launch_tpu.sh's
    ``append_attempt`` shell function writes, so drill- and
    launcher-produced ledgers are interchangeable."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    rec = {"kind": "attempt", "run_id": run_id, "mode": mode,
           "attempt": int(attempt), "start_ts": start_ts,
           "end_ts": end_ts, "rc": int(rc), "verdict": verdict}
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


# ----------------------------------------------------------- the drill


# Same workload shape as the elastic acceptance drills
# (tests/test_elastic.py): 8 steps/epoch, a sharded-manifest save every
# 3 steps, per-step dispatch (log_every 2 and ckpt_every 3 share no
# divisor > 1), kill at step 5 — so the committed step is 3 and the
# beacon-recorded progress 5: exactly 2 steps lost, deterministically.
DRILL_FLAGS = ["--epochs", "1", "--train-batch-size", "8",
               "--n-samples", "64", "--log-every", "2", "--lr", "1e-2",
               "--seed", "3", "--ckpt-mode", "sharded", "--ckpt-sync",
               "--ckpt-every-steps", "3"]
DRILL_KILL = "0:5"
DRILL_RUN_ID = "goodput-drill"


def run_drill(run_dir: str, *, python: Optional[str] = None,
              backoff_base_s: float = 0.2,
              timeout_s: float = 600.0) -> List[Dict[str, Any]]:
    """The scripted kill→requeue→resume drill: run the REAL train CLI
    twice in subprocesses (attempt 0 dies to a scripted preemption at
    step 5 after the step-3 manifest committed; the requeue policy
    classifies it; attempt 1 runs ``--resume auto``), writing
    ``attempts.jsonl`` around each invocation exactly as the launcher
    does. Returns the attempt records. The subprocesses need jax; this
    process stays jax-free."""
    import subprocess

    from tpudist.elastic import policy

    os.makedirs(run_dir, exist_ok=True)
    attempts_path = os.path.join(run_dir, ATTEMPTS_NAME)
    if os.path.exists(attempts_path):
        os.remove(attempts_path)    # a re-run starts a fresh ledger
    python = python or sys.executable

    def run_attempt(extra_flags, env_extra):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["TPUDIST_RUN_ID"] = DRILL_RUN_ID
        env.update(env_extra)
        start = time.time()
        proc = subprocess.run(
            [python, "-m", "tpudist.train", "--save-dir", run_dir,
             *DRILL_FLAGS, *extra_flags],
            env=env, capture_output=True, text=True, timeout=timeout_s)
        return proc, start, time.time()

    p0, s0, e0 = run_attempt([], {"TPUDIST_TEST_KILL": DRILL_KILL})
    if p0.returncode != 113:
        raise RuntimeError(
            f"drill attempt 0 exited {p0.returncode}, expected the "
            f"scripted kill's 113:\n{p0.stdout}\n{p0.stderr}")
    decision = policy.decide(p0.returncode, attempt=0, max_requeues=2,
                             flightrec_dir=run_dir,
                             base_s=backoff_base_s)
    append_attempt(attempts_path, attempt=0, start_ts=s0, end_ts=e0,
                   rc=p0.returncode, verdict=decision.verdict,
                   run_id=DRILL_RUN_ID)
    if not decision.requeue:
        raise RuntimeError(f"drill policy refused to requeue: "
                           f"{decision.shell_line()}")
    time.sleep(decision.backoff_s)    # the measured off-pod gap
    p1, s1, e1 = run_attempt(["--resume", "auto",
                              "--requeue-attempt", "1"], {})
    append_attempt(attempts_path, attempt=1, start_ts=s1, end_ts=e1,
                   rc=p1.returncode,
                   verdict=SUCCESS if p1.returncode == 0 else "crash",
                   run_id=DRILL_RUN_ID)
    if p1.returncode != 0:
        raise RuntimeError(
            f"drill attempt 1 exited {p1.returncode}:\n"
            f"{p1.stdout}\n{p1.stderr}")
    if "tpudist: resume success" not in p1.stdout:
        raise RuntimeError(
            f"drill attempt 1 did not resume from the manifest:\n"
            f"{p1.stdout}")
    return load_attempts(attempts_path)


# -------------------------------------------------------------- the CLI


def _summary_lines(ledger: Dict[str, Any]) -> List[str]:
    frac = ledger.get("goodput_fraction")
    totals = ledger.get("totals") or {}
    lines = [
        f"tpudist: goodput {ledger['goodput_status']}: "
        + (f"{100 * frac:.1f}% productive" if frac is not None
           else "nothing measured")
        + f" of {ledger['total_wall_s']:.2f}s wall across "
          f"{len(ledger['attempts'])} attempt(s), "
          f"{ledger['lost_steps']} step(s) lost to preemption",
        "tpudist: goodput buckets: " + ", ".join(
            f"{k} {totals.get(k, 0.0):.2f}s" for k in BUCKETS),
        f"tpudist: goodput partition "
        f"{'exact' if ledger['exact'] else 'INEXACT'} "
        f"(tolerance {ledger['tolerance']:.0%})",
    ]
    for p in ledger.get("problems", []):
        lines.append(f"tpudist: goodput problem: {p}")
    return lines


def _atomic_write(path: str, payload: str) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        f.write(payload)
    os.replace(tmp, path)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpudist.obs.goodput",
        description="cross-attempt goodput ledger from attempts.jsonl "
                    "+ metrics.jsonl + heartbeat beacons (jax-free)")
    p.add_argument("--run-dir", type=str, default=".",
                   help="directory holding attempts.jsonl, "
                        "metrics.jsonl (top level or attempt<N>/ "
                        "subdirs) and heartbeat beacons")
    p.add_argument("--attempts", type=str, default=None,
                   help="explicit attempts.jsonl path (default: "
                        "<run-dir>/attempts.jsonl)")
    p.add_argument("--out", type=str, default=None,
                   help=f"ledger JSON path (default: <run-dir>/"
                        f"{LEDGER_NAME})")
    p.add_argument("--bench-out", type=str, default=None,
                   help="also write BENCH_GOODPUT.json (BENCH_* "
                        "harness shape, headline = goodput fraction)")
    p.add_argument("--prom-out", type=str, default=None,
                   help="also write tpudist_goodput_* gauges as a "
                        "Prometheus textfile-collector file")
    p.add_argument("--tolerance", type=float, default=TOLERANCE,
                   help=f"partition-exactness tolerance as a fraction "
                        f"of total wall (default {TOLERANCE})")
    p.add_argument("--drill", action="store_true",
                   help="first run the scripted kill->requeue->resume "
                        "drill into --run-dir (real train CLI in "
                        "subprocesses, attempts.jsonl written like the "
                        "launcher's), then build the ledger from it")
    args = p.parse_args(argv)

    if args.drill:
        run_drill(args.run_dir)

    ledger = build_from_dir(args.run_dir, attempts_path=args.attempts,
                            tolerance=args.tolerance)
    if ledger is None:
        path = args.attempts or os.path.join(args.run_dir, ATTEMPTS_NAME)
        print(f"tpudist.obs.goodput: no attempt records at {path} — "
              f"the launcher (or --drill) writes attempts.jsonl",
              file=sys.stderr)
        return 2

    _atomic_write(args.out or os.path.join(args.run_dir, LEDGER_NAME),
                  json.dumps(ledger, indent=1))
    if args.bench_out:
        _atomic_write(args.bench_out,
                      json.dumps(bench_artifact(ledger), indent=1))
    if args.prom_out:
        _atomic_write(args.prom_out, prometheus_text(ledger))
    for line in _summary_lines(ledger):
        print(line)
    # advisory gate (the fraction's status never flips the exit code);
    # a broken PARTITION is a real failure — the whole point is exact
    # accounting
    return 0 if ledger["exact"] else 1


if __name__ == "__main__":
    sys.exit(main())
