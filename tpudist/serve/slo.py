"""Latency accounting + SLO verdicts for the serving engine.

Stdlib-only by design, like :mod:`tpudist.rules`: the offline report CLI
(:mod:`tpudist.obs.report`) folds the serving section with jax
uninstalled, and the thresholds themselves live in the shared rules
table so the serve loop's on-line alerts, the exit verdict line, and the
offline report all grade the SAME numbers against the SAME gates.

The three serving observables:

* **TTFT** — time-to-first-token per request: arrival → the prefill
  dispatch that produced its first token (queue wait included — an
  admission-starved pod must read as a TTFT problem, not disappear into
  engine-only timing).
* **ITL** — inter-token latency: decode tokens come k-per-dispatch
  (the compiled superstep), so each token in a dispatch is attributed
  ``dispatch_wall / k`` — the honest amortised figure at superstep
  granularity (``k=1`` recovers true per-token timing).
* **tokens/s/chip** — generated tokens (first tokens included) over the
  serving wall clock, per chip.
* **shed fraction** — the resilience plane's admission gate (PR 15):
  (shed + expired + rejected) / arrived, graded against
  ``TPUDIST_SERVE_SHED_MAX`` — admitted-traffic latency stays honest
  only because overload is shed, so the shed share is itself gated.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from tpudist import rules as rules_lib

SUCCESS = "success"      # mirrors tpudist.verdict vocabulary without
FAIL = "fail"            # the import (same pattern as obs.alerts)
UNGATEABLE = "ungateable"

# The serve gates, in grading order; each is (rule name, summary key).
# serve_shed (the resilience plane's admission gate) grades the shed
# share of all arrivals: a pod turning away more than the ceiling is
# under-provisioned even when every ADMITTED request met its latency
# SLO — bounded TTFT bought by unbounded shedding is not a pass.
SERVE_RULES = (("ttft", "ttft_p99_s"),
               ("itl", "itl_p99_s"),
               ("tokens_per_chip", "tokens_per_sec_per_chip"),
               ("serve_shed", "shed_fraction"))

# Fixed Prometheus-native histogram buckets (upper bounds, seconds).
# Pinned here — NOT configurable — because bucket bounds are part of the
# metric contract: a scrape-side PromQL histogram_quantile() over two
# runs is only comparable when both used the same edges. TTFT spans
# queue wait + prefill (hundreds of ms under load), ITL is a per-token
# share of one decode dispatch (single-digit ms on real hardware).
TTFT_BUCKETS_S = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)
ITL_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)


def hist_block(samples: List[float],
               buckets: tuple) -> Dict[str, Any]:
    """A self-describing histogram record for one latency family:
    per-bucket (NOT cumulative) counts with one overflow bin, plus
    sum/count. Carried on ``kind=serve_tick`` records so the live
    Prometheus exporter can emit native ``_bucket{le=...}`` series
    without holding raw samples; the bucket edges ride along so every
    consumer renders the same edges the producer counted against."""
    counts = [0] * (len(buckets) + 1)
    total = 0.0
    for s in samples:
        total += s
        for j, ub in enumerate(buckets):
            if s <= ub:
                counts[j] += 1
                break
        else:
            counts[-1] += 1
    return {"buckets": [float(b) for b in buckets], "counts": counts,
            "sum": round(total, 6), "count": len(samples)}


def percentile(xs: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on no samples.
    Deterministic and interpolation-free — two graders computing p99 of
    the same samples must get the same number bit-for-bit."""
    if not xs:
        return None
    s = sorted(xs)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(rank, len(s)) - 1]


@dataclass
class LatencyStats:
    """Per-run latency sample sink; all samples in seconds."""

    ttft_s: List[float] = field(default_factory=list)
    itl_s: List[float] = field(default_factory=list)
    e2e_s: List[float] = field(default_factory=list)

    def note_ttft(self, s: float) -> None:
        self.ttft_s.append(float(s))

    def note_itl(self, s: float, n: int = 1) -> None:
        self.itl_s.extend([float(s)] * max(int(n), 0))

    def note_e2e(self, s: float) -> None:
        self.e2e_s.append(float(s))

    def summary(self) -> Dict[str, Any]:
        return {
            "ttft_p50_s": percentile(self.ttft_s, 50),
            "ttft_p99_s": percentile(self.ttft_s, 99),
            "itl_p50_s": percentile(self.itl_s, 50),
            "itl_p99_s": percentile(self.itl_s, 99),
            "e2e_p50_s": percentile(self.e2e_s, 50),
            "e2e_p99_s": percentile(self.e2e_s, 99),
        }

    def ttft_hist(self) -> Dict[str, Any]:
        return hist_block(self.ttft_s, TTFT_BUCKETS_S)

    def itl_hist(self) -> Dict[str, Any]:
        return hist_block(self.itl_s, ITL_BUCKETS_S)


def rule_status(rule: str, value: Optional[float]) -> str:
    """Three-valued per-gate verdict: no measurement is UNGATEABLE (the
    convention every tpudist gate follows — an empty run must not read
    as an SLO pass), else SUCCESS/FAIL by the shared rules table (env
    overrides read at call time)."""
    if value is None:
        return UNGATEABLE
    return FAIL if rules_lib.breached(rule, value) else SUCCESS


def grade(ttft_p99_s: Optional[float], itl_p99_s: Optional[float],
          tokens_per_sec_per_chip: Optional[float],
          shed_fraction: Optional[float] = None) -> Dict[str, str]:
    """All four serve gates + the fold: overall ``status`` is FAIL if
    any gate fails, UNGATEABLE if nothing was measurable, else
    SUCCESS. ``shed_fraction`` is None on pre-resilience artifacts (and
    empty runs) — the serve_shed gate reads UNGATEABLE there, never a
    retroactive fail."""
    vals = {"ttft_p99_s": ttft_p99_s, "itl_p99_s": itl_p99_s,
            "tokens_per_sec_per_chip": tokens_per_sec_per_chip,
            "shed_fraction": shed_fraction}
    out = {f"{rule}_status": rule_status(rule, vals[key])
           for rule, key in SERVE_RULES}
    statuses = list(out.values())
    if FAIL in statuses:
        overall = FAIL
    elif all(s == UNGATEABLE for s in statuses):
        overall = UNGATEABLE
    else:
        overall = SUCCESS
    out["status"] = overall
    return out


def serve_status(ttft_p99_s: Optional[float], itl_p99_s: Optional[float],
                 tokens_per_sec_per_chip: Optional[float]) -> str:
    """The folded serving verdict alone (what ``verdict.serve_status``
    delegates to)."""
    return grade(ttft_p99_s, itl_p99_s, tokens_per_sec_per_chip)["status"]


def slo_block(summary: Dict[str, Any]) -> Dict[str, Any]:
    """The ``slo`` block of the serve CLI's ``--bench-out`` document,
    from a ``run_serve`` summary. Thresholds resolve through the rules
    table at call time, like every other gate."""
    return {
        "status": summary["status"],
        **{f"{rule}_status": summary[f"{rule}_status"]
           for rule, _ in SERVE_RULES},
        "thresholds": {rule: rules_lib.resolve(rule)
                       for rule, _ in SERVE_RULES},
    }
