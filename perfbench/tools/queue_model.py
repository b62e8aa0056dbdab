"""What a schedule drawn from ``--seed`` would cost in spread: a model of
``run_serve``'s loop on the host (no chip, no program): admit while a slot
and pages are free, one fenced prefill each, then one decode dispatch of
``decode_k`` tokens for every busy slot, with the two times the chip
measured for the cell (prefill 41.6 ms, dispatch 171 ms: PERF.md 5). It
plays the mix as the benchmark offers it (``schedule``: one trace, so no
spread) and as a seed could draw it: ``order`` (the same sizes, shuffled,
and fresh Poisson gaps), ``blocks`` (the same sizes and the same gaps, in
another order inside blocks of 16 that each span all the quantiles). For
each it prints the median over seeds and, over sets of six seeds, the
spread a bound would be set from. It agrees with the chip on the committed
schedule (PERF.md 4), which is all it is trusted for: how much the ORDER
moves a metric, not the metric.

    python3 perfbench/tools/queue_model.py [--seconds 40] [--seeds 48]
    python3 perfbench/tools/queue_model.py --modes
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import stats, traffic      # noqa: E402

T_PREFILL, T_DISPATCH = 0.0416, 0.171


def drawn(kind: str, mix: dict, seed: int, seconds: float, pad: int):
    if kind == "schedule":
        return traffic.schedule(mix, seconds, pad)
    if kind == "order":
        return traffic.schedule(dict(mix, schedule_seed=seed), seconds, pad)
    arr, plen, olen = traffic.schedule(mix, seconds, pad)
    n, rng = len(arr), np.random.default_rng([seed, 23])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    nb = max(1, n // 16)

    def blocks(x):
        x = np.sort(x)
        return np.concatenate([x[rng.permutation(np.arange(j, n, nb))]
                               for j in range(nb)])
    arr = np.cumsum(blocks(gaps))
    return arr * (seconds / arr[-1]), blocks(plen), blocks(olen)


def play(arr, plen, olen, e: dict, td: float = T_DISPATCH,
         tp: float = T_PREFILL, rng=None, jitter: float = 0.0) -> dict:
    """One run. ``td`` / ``tp``: this machine's dispatch and prefill times;
    ``jitter``: the standard deviation, in seconds, drawn from ``rng`` onto
    each of them."""
    n, k, pt = len(arr), e["decode_k"], e["page_tokens"]

    def wobble():
        return rng.normal(0.0, jitter) if jitter else 0.0
    t, nxt, used, waiting = 0.0, 0, 0, []
    slot = [None] * e["slots"]          # [request, generated, pages]
    first, done = {}, {}
    while len(done) < n:
        for i in range(e["slots"]):
            while nxt < n and arr[nxt] <= t:
                waiting.append(nxt)
                nxt += 1
            if slot[i] is not None or not waiting:
                continue
            r, need = waiting[0], -(-plen[waiting[0]] // pt)
            if used + need > e["pages"]:
                break
            waiting.pop(0)
            t += tp + wobble()
            first[r], slot[i], used = t, [r, 1, need], used + need
        busy = [s for s in slot if s is not None]
        if not busy:
            t = max(t, arr[nxt])
            continue
        for s in busy:
            need = min(plen[s[0]] + s[1] + k - 2, e["max_seq"] - 1) // pt + 1
            used, s[2] = used + max(need - s[2], 0), max(need, s[2])
        t += td + wobble()
        for i, s in enumerate(slot):
            if s is not None:
                s[1] = min(s[1] + k, olen[s[0]])
                if s[1] >= olen[s[0]]:
                    done[s[0]], used, slot[i] = t, used - s[2], None
    tpot = [(done[r] - first[r]) / (olen[r] - 1) for r in range(n)
            if olen[r] >= 2]
    return {"ttft_p95_ms": 1e3 * stats.percentile(
                [first[r] - arr[r] for r in range(n)], 95),
            "tpot_p95_ms": 1e3 * stats.percentile(tpot, 95),
            "tokens_per_s": float(sum(olen)) / (max(done.values()) - arr[0])}


def modes(seconds: float, runs: int = 24, jitter: float = 0.0003) -> None:
    """The committed open schedule on machines whose dispatch and prefill
    times differ by tenths of a percent: where ``tpot_p95_ms`` sits, and
    how far ``runs`` runs of one machine spread (PERF.md 6, the check's
    refusal)."""
    mix = traffic.load("chat-open")
    e = mix["engine"]
    sched = traffic.schedule(mix, seconds, e["prompt_pad"])
    rng = np.random.default_rng(2)
    for td in (0.1704, 0.1707, 0.1709, 0.1710, 0.1711, 0.1713, 0.1716):
        for tp in (0.0412, 0.0415, 0.0416, 0.0418, 0.0421):
            for m in ("tpot_p95_ms", "ttft_p95_ms"):
                v = [play(*sched, e, td, tp, rng, jitter)[m]
                     for _ in range(runs)]
                print(f"dispatch {1e3 * td:.1f} ms prefill {1e3 * tp:.1f} "
                      f"ms: {m} median {statistics.median(v):.2f} "
                      f"[{min(v):.2f}, {max(v):.2f}] spread "
                      f"{100 * stats.iqr_share(v):.2f} %", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", type=int, default=48)
    ap.add_argument("--modes", action="store_true",
                    help="scan machine timings instead of seeds")
    a = ap.parse_args()
    if a.modes:
        modes(a.seconds)
        return 0
    for name in ("chat-open", "chat-sat"):
        mix = traffic.load(name)
        e = mix["engine"]
        rates = (mix["rate_rps"], 0.8 * mix["knee_rps"]) \
            if name == "chat-open" else (mix["rate_rps"],)
        for rate in rates:
            for kind in ("schedule", "order", "blocks"):
                runs = [play(*drawn(kind, dict(mix, rate_rps=rate), s,
                                    a.seconds, e["prompt_pad"]), e)
                        for s in range(100, 100 + a.seeds)]
                row = f"{name} {rate:g}/s {kind:8s}"
                for m in runs[0]:
                    v = [r[m] for r in runs]
                    sets = [stats.iqr_share(v[i:i + 6])
                            for i in range(0, len(v) - 5, 6)]
                    row += (f" | {m} {statistics.median(v):.1f} "
                            f"[{min(v):.1f}, {max(v):.1f}] spread of six "
                            f"{100 * statistics.median(sets):.2f} % "
                            f"(widest {100 * max(sets):.2f} %)")
                print(row, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
