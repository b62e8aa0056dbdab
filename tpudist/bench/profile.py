"""Step profiler: where does the train step's time actually go?

The reference has no profiling at all (SURVEY.md §5.1 — its only timing is
CI's 10-second job polling); ``--profile-dir`` already captures raw
``jax.profiler`` traces for TensorBoard. This tool closes the loop ON the
TPU host with no UI: it traces a few steps of the configured workload,
parses the XLA op stats out of the xplane protobuf, and prints a
per-category and per-op table with achieved FLOP rates and memory
bandwidths — the exact analysis that found the RoPE HBM round-trip this
framework's flash kernels now avoid.

Run:  python -m tpudist.bench.profile [--model transformer] [--steps 5]
          [any tpudist.train model/shape flags] [--out profile.json]

Requires the ``xprof`` package (ships with the tensorboard profiler
plugin) for trace parsing; exits with a clear message when absent. The
trace itself always lands in ``--trace-dir`` for TensorBoard regardless.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from collections import defaultdict
from typing import Optional


def parse_hlo_stats(trace_dir: str):
    """xplane.pb files under ``trace_dir`` → list of per-op dicts."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError as e:
        raise RuntimeError(
            "trace parsing needs the 'xprof' package (tensorboard profiler "
            "plugin); the raw trace is in "
            f"{trace_dir} for TensorBoard") from e
    paths = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no xplane.pb found under {trace_dir}")
    data, _ = raw_to_tool_data.xspace_to_tool_data(paths, "hlo_stats", {})
    table = json.loads(data.decode() if isinstance(data, bytes) else data)
    cols = [c["id"] for c in table["cols"]]
    return [dict(zip(cols, (c.get("v") for c in row["c"])))
            for row in table["rows"]]


def summarize(ops, n_steps: int, top: int = 15) -> dict:
    """Aggregate op stats into per-category and top-op tables (µs/step)."""
    by_cat = defaultdict(float)
    total = 0.0
    for op in ops:
        t = float(op.get("total_self_time") or 0) / n_steps
        by_cat[op.get("category")] += t
        total += t
    top_ops = sorted(ops, key=lambda o: -float(o.get("total_self_time")
                                               or 0))[:top]
    return {
        "total_us_per_step": round(total, 1),
        "by_category_us": {k: round(v, 1) for k, v in
                           sorted(by_cat.items(), key=lambda kv: -kv[1])},
        "top_ops": [{
            "us_per_step": round(float(o.get("total_self_time") or 0)
                                 / n_steps, 1),
            "category": o.get("category"),
            "name": o.get("hlo_op_name"),
            "bound_by": o.get("bound_by"),
            "gflops_per_sec": o.get("model_flop_rate"),
            "mem_bw_gbps": o.get("measured_memory_bw"),
        } for o in top_ops],
    }


def profile_step(cfg, trace_dir: str, n_steps: int = 5):
    """Trace ``n_steps`` steady-state train steps of ``cfg``'s workload."""
    import jax

    from tpudist import data as data_lib
    from tpudist import engine
    from tpudist.parallel import build_mesh
    from tpudist.parallel import sharding as shd

    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(cfg.seed), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    if cfg.model.name == "mlp":
        x, y = data_lib.make_synthetic_data(
            cfg.batch_size, cfg.data.n_features, cfg.data.seed)
        batch = shd.put_batch(mesh, (x, y))
    else:
        toks = data_lib.make_synthetic_tokens(
            cfg.batch_size, cfg.model.max_seq_len + 1,
            cfg.model.vocab_size, cfg.data.seed)
        batch = shd.put_batch(mesh, (toks,))
    for _ in range(3):                       # compile + warm
        state, loss = step(state, batch)
    float(loss)
    jax.profiler.start_trace(trace_dir)
    for _ in range(n_steps):
        state, loss = step(state, batch)
    float(loss)                              # fence inside the trace
    jax.profiler.stop_trace()


def main(argv: Optional[list] = None) -> int:
    from tpudist.config import parse_args
    from tpudist.utils import tune_tpu
    tune_tpu()

    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--trace-dir", type=str, default=None)
    p.add_argument("--out", type=str, default=None,
                   help="also write the summary as JSON here")
    own, rest = p.parse_known_args(argv)
    if own.steps < 1:
        p.error("--steps must be >= 1")
    cfg = parse_args(rest)

    trace_dir = own.trace_dir or tempfile.mkdtemp(prefix="tpudist_prof_")
    profile_step(cfg, trace_dir, n_steps=own.steps)
    try:
        ops = parse_hlo_stats(trace_dir)
    except RuntimeError as e:
        print(f"tpudist.bench.profile: {e}", file=sys.stderr)
        return 1
    s = summarize(ops, own.steps, top=own.top)

    print(f"trace: {trace_dir}")
    print(f"total: {s['total_us_per_step']:.0f} us/step")
    print(f"{'us/step':>9}  {'%':>5}  category")
    denom = s["total_us_per_step"] or 1.0   # all-zero times: CPU xplanes
    for cat, us in s["by_category_us"].items():
        print(f"{us:9.0f}  {100 * us / denom:5.1f}  {cat}")
    print(f"\n{'us/step':>9}  {'bound':>8}  {'GF/s':>8}  {'GB/s':>7}  op")
    for o in s["top_ops"]:
        print(f"{o['us_per_step']:9.0f}  {str(o['bound_by'])[:8]:>8}  "
              f"{str(o['gflops_per_sec'])[:8]:>8}  "
              f"{str(o['mem_bw_gbps'])[:7]:>7}  {o['name']}")
    if own.out:
        with open(own.out, "w") as f:
            json.dump(s, f, indent=1)
        print(f"\nwrote {own.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
