"""The one general traffic generator. A traffic mix is a data file under
``perfbench/traffic/``. The schedule (the multiset of sizes at the fixed
quantiles of the mix's length distributions, their order, and the Poisson
gaps rescaled to span exactly the window) is drawn from the mix's own
``schedule_seed``: every ``--seed`` replays the same trace and differs in
the token ids (and the weights). A schedule drawn from ``--seed`` changes
the work between seeds, whichever way it is drawn (the same sizes and gaps
in another order, whole or in blocks): 2 to 4 % between the quartiles of
six seeds in every serve metric, which no bound of 10 % holds
(``perfbench/tools/queue_model.py``, PERF.md section 4)."""

from __future__ import annotations

import json
import os
import statistics

import numpy as np

from perfbench.lib.manifest import BENCH_DIR


def load(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """n lengths at the evenly spaced quantiles of a clipped lognormal: the
    same multiset for every seed."""
    nd = statistics.NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def schedule(mix: dict, seconds: float, prompt_pad: int):
    """(arrivals_s, prompt_lens, output_lens) of the mix over ``seconds``.
    Open loop: Poisson gaps, rescaled so that the last arrival falls at
    ``seconds`` (the offered rate is exact in every run)."""
    rng = np.random.default_rng([int(mix["schedule_seed"]), 23])
    n = max(2, int(round(mix["rate_rps"] * seconds)))
    p, o = mix["prompt_len"], mix["output_len"]
    plen = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                min(p["max"], prompt_pad))
    olen = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    rng.shuffle(plen)
    rng.shuffle(olen)
    gaps = rng.exponential(1.0, size=n)
    arrivals = np.cumsum(gaps)
    return arrivals * (seconds / arrivals[-1]), plen, olen


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   prompt_pad: int):
    """[(arrival_s, tokens(prompt_pad,), prompt_len, max_new)], sorted by
    arrival: the mix's schedule, with token ids drawn uniformly from the
    seed."""
    arrivals, plen, olen = schedule(mix, seconds, prompt_pad)
    n = len(arrivals)
    toks = np.random.default_rng([int(seed), 29]).integers(
        0, vocab, size=(n, prompt_pad)).astype(np.int32)
    out = []
    for i in range(n):
        t = toks[i].copy()
        t[plen[i]:] = 0
        out.append((float(arrivals[i]), t, int(plen[i]), int(olen[i])))
    return out


def train_batches(seed: int, n_samples: int, seq_len: int, vocab: int,
                  batch_size: int, steps: int) -> np.ndarray:
    """(steps, batch_size, seq_len + 1): the first batches of epoch 0 of
    the acceptance job's synthetic token stream, drawn HERE from the seed
    and not read back from the program: every row starts on a token drawn
    from ``default_rng(seed)`` and continues ``t -> (7 t + 3) mod vocab``;
    epoch 0 takes the rows in the order of ``jax.random.permutation`` under
    ``fold_in(PRNGKey(seed), 0)``. What the program staged is compared with
    these rows (``rows_mismatch``), and the reference follows these."""
    import jax
    first = np.random.default_rng(seed).integers(
        0, vocab, size=(n_samples, 1), dtype=np.int32)[:, 0]
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(jax.random.PRNGKey(seed), 0), n_samples))
    rows = np.empty((steps * batch_size, seq_len + 1), np.int32)
    rows[:, 0] = first[perm[:steps * batch_size]]
    for t in range(1, seq_len + 1):
        rows[:, t] = (rows[:, t - 1] * 7 + 3) % vocab
    return rows.reshape(steps, batch_size, seq_len + 1)
