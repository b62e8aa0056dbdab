"""MFU / roofline accounting from the compiled program itself.

An *analytic* MFU counts FLOPs from the model formula
(``perfbench/lib/flops.py`` keeps that one, with the benchmark). The
training run can do better: the superstep is already compiled, and
XLA's cost analysis on that exact executable
(``Compiled.cost_analysis()``) reports the FLOPs and bytes the program
actually executes — remat recompute, masked padding steps, fused
epilogues and all. Divided by the ``StepTimer``'s steady-state wall
time, that yields model-FLOP utilization and achieved HBM bytes/s per
chip with no model-specific formula to drift out of date.

The per-chip convention: ``cost_analysis`` describes the per-device SPMD
program, and ``StepTimer`` wall time is the same on every host, so
``flops / k / step_s`` IS the per-chip achieved rate.

The bf16 peak table lives here; ``TPUDIST_PEAK_TFLOPS`` overrides it
for chips the table does not know, and makes MFU testable on the CPU
backend.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, Optional

# bf16 peak TFLOP/s by device kind (dense); no match → MFU UNGATEABLE
PEAK_TFLOPS = [
    (re.compile(r"v5 ?lite|v5e", re.I), 197.0),
    (re.compile(r"v5p", re.I), 459.0),
    (re.compile(r"v4", re.I), 275.0),
    (re.compile(r"v6|trillium", re.I), 918.0),
]


def chip_peak_tflops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 TFLOP/s for ``device_kind`` (default: local device 0).
    ``TPUDIST_PEAK_TFLOPS`` overrides the table — required to account a
    chip generation the table predates, and how CPU tests pin MFU."""
    env = os.environ.get("TPUDIST_PEAK_TFLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass   # malformed override must not fail a finished run;
            # fall through to the table
    if device_kind is None:
        try:
            import jax
            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    for pat, peak in PEAK_TFLOPS:
        if pat.search(device_kind):
            return peak
    return None


def dispatch_cost(fn: Any) -> Optional[Dict[str, Any]]:
    """The compiled cost-analysis dict of a train-step/superstep callable
    built by :mod:`tpudist.engine` (they expose ``.cost_analysis()``
    after their first dispatch), or None when unavailable."""
    cost_fn = getattr(fn, "cost_analysis", None)
    if cost_fn is None:
        return None
    try:
        return cost_fn()
    except Exception:
        return None


def mfu_fields(cost: Optional[Dict[str, Any]],
               step_s: float) -> Dict[str, Any]:
    """Roofline fields for the ``kind=timing`` record.

    ``cost`` is the dispatch program's cost analysis and is treated as
    covering ONE train step regardless of the superstep length k: XLA's
    HLO cost analysis visits a while/scan body ONCE (the trip count is
    not multiplied in), so the k-step ``lax.scan`` superstep reports the
    same flops as the k=1 per-step program — measured identical to
    within the scan's ~10-flop bookkeeping, and pinned by
    tests/test_obs.py so a cost-model change in a future XLA cannot
    silently skew MFU by k×. (Known undercount, same mechanism: a
    gradient-accumulation microbatch scan inside the step counts once
    too — MFU is advisory, not exit-code-bearing.)

    ``step_s`` is the steady-state seconds per step from ``StepTimer``.
    All fields are present in every record — ``None`` marks "could not
    be derived" (no cost analysis, no steady-state steps, unknown chip
    peak) so downstream parsers never key-error on a degraded run.
    """
    out: Dict[str, Any] = {
        "model_flops_per_step": None, "hbm_bytes_per_step": None,
        "achieved_tflops_per_chip": None, "achieved_gbps_per_chip": None,
        "peak_tflops": chip_peak_tflops(), "mfu": None,
    }
    if not cost or step_s <= 0:
        return out
    flops = cost.get("flops")
    nbytes = cost.get("bytes accessed")
    if flops and flops > 0:
        per_step = float(flops)
        out["model_flops_per_step"] = per_step
        achieved = per_step / step_s
        out["achieved_tflops_per_chip"] = achieved / 1e12
        peak = out["peak_tflops"]
        if peak:
            out["mfu"] = achieved / (peak * 1e12)
    if nbytes and nbytes > 0:
        per_step_b = float(nbytes)
        out["hbm_bytes_per_step"] = per_step_b
        out["achieved_gbps_per_chip"] = per_step_b / step_s / 1e9
    return out
