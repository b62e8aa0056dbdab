"""Set-up by what the program was doing. Set-up is the interval from the
process's start to the window's opening (``view["window_us"][0]``): the
same interval ``run.py`` reads as ``setup_s``, the process's start taken
from ``run.process_age_s`` and placed on ``perf_counter``, the clock of
the spans and of the window. It is cut at every boundary of the
program's spans, and each piece is booked to the INNERMOST span that
covers it (the shortest, as ``layers.innermost`` books: a compile phase
wins over the ``warmup_program`` it ran under, and compile phases nested
in one another count once). Reported in milliseconds:

* ``params.names``: the pieces whose innermost span has one of the names;
* ``params.before_first``: from the process's start to the first span;
* ``params.unattributed``: after the first span, under no span.

Where the metrics' names cover every span of set-up, the three kinds
partition it. A program without a span of the names (an older commit)
reads 0.0 there, and the whole of set-up before its first span."""

import heapq
import time

from perfbench import run as run_lib


def process_start_us():
    """The process's start on ``perf_counter``, in microseconds."""
    return (time.perf_counter() - run_lib.process_age_s()) * 1e6


def innermost(lo, hi, spans):
    """{name: us} of ``[lo, hi]`` by the shortest of the ``(t0, t1,
    name)`` spans that covers each piece, "" where none does; ties go to
    the span listed first. One sweep over the sorted boundaries with the
    open spans in a heap by length: thousands of set-up spans cost a
    sort, not a scan of every span for every piece."""
    cuts = sorted({lo, hi} | {t for s in spans for t in s[:2]
                              if lo < t < hi})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    out, live, j = {}, [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(order) and spans[order[j]][0] <= a:
            t0, t1, name = spans[order[j]]
            heapq.heappush(live, (t1 - t0, order[j], t1, name))
            j += 1
        while live and live[0][2] <= a:
            heapq.heappop(live)
        name = live[0][3] if live else ""
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def booked(view):
    """``(lo, first, hi, {name: us})`` of set-up, "" for the time under
    no span after the first; computed once a view. None where the
    process's start does not come before the window (no /proc:
    ``process_age_s`` then counts from its module's import)."""
    if "setup_by_span" in view:
        return view["setup_by_span"]
    view["setup_by_span"] = None
    lo = process_start_us()
    hi = view["window_us"][0]
    if lo >= hi:
        return None
    spans = [(s["t0_us"], s["t1_us"], s["name"]) for s in view["spans"]
             if s["t1_us"] > s["t0_us"] and s["t0_us"] < hi
             and s["t1_us"] > lo]
    first = max(lo, min((s[0] for s in spans), default=hi))
    by = innermost(first, hi, spans) if hi > first else {}
    view["setup_by_span"] = (lo, first, hi, by)
    backend = [s.get("args", {}).get("cache", "-") for s in view["spans"]
               if s["name"] == "compile_backend" and lo < s["t0_us"] < hi]
    print(f"perfbench: set-up {(hi - lo) / 1e3:.1f} ms, {len(spans)} spans "
          f"({len(backend)} backend compiles: {backend.count('hit')} cache "
          f"hits, {backend.count('miss')} misses); before the first span "
          f"{(first - lo) / 1e3:.1f} ms; ms by innermost span: " + ", ".join(
              f"{k or '(none)'} {v / 1e3:.1f}" for k, v in sorted(
                  by.items(), key=lambda kv: -kv[1])), flush=True)
    return view["setup_by_span"]


def read(view, params, peaks):
    got = booked(view)
    if got is None:
        return None
    lo, first, _, by = got
    if params.get("before_first"):
        return (first - lo) / 1e3
    if params.get("unattributed"):
        return by.get("", 0.0) / 1e3
    return sum(by.get(n, 0.0) for n in params["names"]) / 1e3
