"""Median, over the window's dispatches, of the wall from the start of a
``dispatch`` span to the end of the ``fence`` span that drained it, per
step it covered."""

from perfbench.lib import stats


def read(view, params, peaks):
    lo, hi = view["window_us"]
    spans = sorted((s for s in view["spans"]
                    if s["name"] in ("dispatch", "fence")
                    and s["t1_us"] > lo and s["t0_us"] < hi),
                   key=lambda s: s["t0_us"])
    xs, start = [], None
    for s in spans:
        if s["name"] == "dispatch":
            start = s["t0_us"] if start is None else start
        elif start is not None and s["args"].get("steps"):
            xs.append((s["t1_us"] - start) / 1e3 / s["args"]["steps"])
            start = None
    return stats.percentile(xs, 50) if xs else None
