"""Headline benchmark: synthetic transformer training throughput + MFU.

Default mode prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "detail"}. ``vs_baseline`` is the ratio of this run's
tokens/s/chip to the best value recorded by any prior round's
``BENCH_r*.json`` in the repo root (1.0 when none exists), so regressions
are visible in the artifact itself. ``detail`` carries an analytic MFU:
FLOPs/token = 6·N_active + 6·L·d·s (dense matmuls fwd+bwd ≈ 6N, plus
causal attention scores/values), against the chip's bf16 peak. N_active
discounts non-routed expert weights for the MoE model (top_k/E of each
expert FFN does useful work per token — the honest convention; the
dispatch/combine einsums are framework overhead, not model FLOPs). The
workload is BASELINE.json config #5 shaped to one chip: Llama-style block
stack (4 layers, 2048 hidden, bf16) full train step (fwd+bwd+Adam) under
jit.

``--matrix`` instead benches the whole perf surface — {seq 512, 2048,
4096} × {plain, fused, chunked LM head} × {flash, no-flash} × {dense,
gqa, moe} (meaningful cells only; see ``MATRIX_ROWS``) — printing one JSONL
line per cell and writing the committed artifact ``BENCH_MATRIX.json``
plus a README-ready markdown table. One command, one artifact: the
reference's everything-is-an-observable-output stance
(reference slurm_train.sbatch:38,43) applied to performance claims.

``--fused-xent`` benches the pallas fused LM-head variant
(tpudist.ops.pallas.fused_xent): it removes the (tokens, vocab) logits
tensor from HBM entirely — batch 96+ trains on one v5e, where the plain
path OOMs. Its FLOP floor is 4 head matmuls vs the plain path's 3 (the
backward must recompute logits once; r4's merged backward kernel reaches
that floor — head-only fwd+bwd at the bench shape measured 95.7 ms vs
113.8 ms for the r3 split kernels and 75.2 ms plain, i.e. 1.27× plain
against the 1.33× FLOP ratio), so at batches where plain fits, plain
stays the default.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import time

import jax

from tpudist import data, engine
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig, flagship_model_config)
from tpudist.obs import mfu as obs_mfu
from tpudist.obs.hbm import HbmSampler

# bf16 peak table lives in tpudist.obs.mfu now (the train run's roofline
# record uses the same source); these aliases keep bench's surface stable
PEAK_TFLOPS = obs_mfu.PEAK_TFLOPS
chip_peak_tflops = obs_mfu.chip_peak_tflops


def _sweep_obs_fields(dispatch_fn, step_ms: float,
                      sampler: HbmSampler) -> dict:
    """The per-point utilization context the sweeps record alongside
    steps/s: compiled-program MFU (obs.mfu — on CPU the peak is unknown
    so mfu is None unless $TPUDIST_PEAK_TFLOPS pins it, but the FLOP and
    byte counts are always real) and the HBM high-water mark so a perf
    point's memory footprint rides in the artifact."""
    sampler.sample()
    f = obs_mfu.mfu_fields(obs_mfu.dispatch_cost(dispatch_fn),
                           step_ms / 1000.0)
    return {"mfu": f["mfu"],
            "model_flops_per_step": f["model_flops_per_step"],
            "achieved_gbps_per_chip": f["achieved_gbps_per_chip"],
            "hbm_peak_bytes": sampler.split()["hbm_peak_bytes"]}


def active_params(params, cfg: TrainConfig) -> int:
    """Parameters doing useful work per token: everything, minus the
    (1 − top_k/E) fraction of each MoE expert weight a token never visits."""
    total = sum(x.size for x in jax.tree.leaves(params))
    m = cfg.model
    if m.name != "moe":
        return total
    layers = params["layers"]
    expert = sum(layers[k].size for k in ("w_gate", "w_up", "w_down"))
    return total - int(expert * (1.0 - m.expert_top_k / m.n_experts))


def train_flops_per_token(n_active: int, cfg: TrainConfig) -> float:
    """6·N for the dense matmuls (fwd 2N + bwd 4N) plus causal attention:
    per layer fwd = 2·(2·s·d)·0.5 (QKᵀ + PV, halved by causality), ×3 for
    fwd+bwd."""
    m = cfg.model
    s = m.max_seq_len
    return 6.0 * n_active + 6.0 * m.n_layers * m.d_model * s


def best_prior_bench() -> float | None:
    """Best tokens/s/chip across prior rounds' BENCH_r*.json, anchored to
    this script's directory (cwd-independent)."""
    here = os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        try:
            with open(path) as f:
                rec = json.load(f)
            val = rec.get("parsed", rec).get("value")
            if isinstance(val, (int, float)) and (best is None or val > best):
                best = float(val)
        except Exception:
            continue
    return best


def build_cfg(*, seq: int, per_chip: int, head: str = "plain",
              model: str = "transformer", remat: bool = False,
              moe_group: int = 256) -> TrainConfig:
    """One matrix cell's TrainConfig. ``head``: plain | fused | cN
    (chunked over N sequence chunks)."""
    n_dev = jax.device_count()
    batch = per_chip * n_dev
    if model == "gqa":
        # grouped-query flagship (16 q heads, 4 kv heads): the compact-kv
        # flash kernels hold the dense model's MFU while the kv
        # projections shrink 4x — BENCH_MATRIX.json row: 105,920 tok/s/chip,
        # 79.67% MFU on v5e at batch 56 (same batch as dense plain)
        mcfg = ModelConfig(name="transformer", vocab_size=32000, n_layers=4,
                           d_model=2048, n_heads=16, n_kv_heads=4,
                           d_ff=5504, max_seq_len=seq)
    elif model == "moe_cf1":
        # capacity_factor 1.0: computed expert rows == counted active rows
        # (cf 1.25 pays 25% extra FFN FLOPs for fewer dropped tokens —
        # a quality/throughput knob, benched as its own row, default kept
        # honest at 1.25). r5 sweep: ~10% step win over cf 1.25.
        mcfg = ModelConfig(name="moe", vocab_size=32000, n_layers=4,
                           d_model=2048, n_heads=16, n_kv_heads=16,
                           d_ff=2752, max_seq_len=seq, n_experts=8,
                           expert_top_k=2, moe_group_size=moe_group,
                           capacity_factor=1.0)
    elif model == "moe_gqa":
        # MoE backbone with grouped-query attention (16 q heads, 4 kv):
        # the two "beyond" model families composed — kv projections shrink
        # 4x on top of the routed FFN
        mcfg = ModelConfig(name="moe", vocab_size=32000, n_layers=4,
                           d_model=2048, n_heads=16, n_kv_heads=4,
                           d_ff=2752, max_seq_len=seq, n_experts=8,
                           expert_top_k=2, moe_group_size=moe_group)
    elif model == "moe":
        # d_ff 2752 per expert: active params/token = attn side + top2/8 of
        # the expert weights ≈ 267M — the same active size as the dense
        # flagship, so the MoE row reads apples-to-apples. (Experts at the
        # dense model's d_ff 5504 total 1.2B params, whose f32 Adam state
        # alone exceeds one v5e's 16 GB HBM past batch 4 — that shape
        # belongs to multi-chip expert parallelism, which the dryrun's
        # expert-axis mesh exercises.) Group 256, batch 32/chip: r4
        # measured optimum on v5e — 70.1k tok/s, 58.1% active-MFU (r3: 66.9k
        # at g512/b24; g128 55.4%, g384 56.7%, b40 53.4%, b48 OOM; an
        # index/gather dispatch prototype measured ~60k — its backward
        # scatter-adds serialize at ~21 GB/s, so the einsum dispatch
        # stays). The remaining gap to the dense 80% is structural at
        # one-chip batch: ~12% extra expert FLOPs from capacity-factor
        # slots (cf·k/E rows computed, k/E counted active), ~3% dispatch/
        # combine einsums, ~19 ms/step of Adam+weight HBM traffic for the
        # 674M TOTAL params (profiled: three ~6.4 ms 630 GB/s fusions),
        # and cap=80-row expert matmuls vs the MXU's appetite. (Total
        # params 674M: 65.5M embed + 67M attn + 541M experts.)
        mcfg = ModelConfig(name="moe", vocab_size=32000, n_layers=4,
                           d_model=2048, n_heads=16, n_kv_heads=16,
                           d_ff=2752, max_seq_len=seq, n_experts=8,
                           expert_top_k=2, moe_group_size=moe_group)
    else:
        mcfg = flagship_model_config(max_seq_len=seq)
    return TrainConfig(
        batch_size=batch, lr=1e-3, seed=0, dtype="bfloat16",
        fused_xent=(head == "fused"), remat=remat,
        # matrix rows pin their strategy (a row labeled "plain" must not
        # silently bench whatever auto picks); fused/cN rows are pinned by
        # their explicit flags below, which "auto" honors; head="auto"
        # benches the policy itself
        lm_head=("plain" if head == "plain" else "auto"),
        xent_chunks=(int(head[1:]) if head.startswith("c") else 0),
        data=DataConfig(n_samples=batch),
        model=mcfg,
        parallel=ParallelConfig(data=-1))


def measure(cfg: TrainConfig, iters: int = 60) -> dict:
    """Steady-state step time of cfg's train step on the live mesh.

    Timing in groups: per-group fencing (a host transfer of the loss)
    keeps the async queue honest, and the 20-step group amortises the
    fence's pipeline drain."""
    from tpudist.parallel import build_mesh
    from tpudist.parallel import sharding as shd
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    n_active = active_params(state.params, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    sampler = HbmSampler(period_s=0)
    step = engine.make_train_step(cfg, mesh)
    seq = cfg.model.max_seq_len
    toks = data.make_synthetic_tokens(cfg.batch_size, seq + 1,
                                      cfg.model.vocab_size, seed=0)
    # place the batch once: steady-state training streams input during the
    # previous step, so per-step host transfer must not pollute the timing
    batch_t = shd.put_batch(mesh, (toks,))

    for _ in range(2):                       # trace + compile + warm
        state, loss = step(state, batch_t)
    float(loss)

    group, n_groups = 20, max(2, iters // 20)
    group_ms = []
    for _ in range(n_groups):
        t0 = time.perf_counter()
        for _ in range(group):
            state, loss = step(state, batch_t)
        float(loss)
        group_ms.append((time.perf_counter() - t0) * 1000 / group)

    n_dev = jax.device_count()
    step_ms = statistics.median(group_ms)
    tok_s_chip = cfg.batch_size * seq / (step_ms / 1000) / n_dev
    device_kind = jax.devices()[0].device_kind
    peak = chip_peak_tflops(device_kind)
    achieved = train_flops_per_token(n_active, cfg) * tok_s_chip / 1e12
    sampler.sample()
    return {
        "hbm_peak_bytes": sampler.split()["hbm_peak_bytes"],
        "device": device_kind,
        "n_devices": n_dev,
        "global_batch": cfg.batch_size,
        "seq_len": seq,
        "n_params": n_params,
        "n_active_params": n_active,
        "tok_s_chip": round(tok_s_chip, 1),
        "mfu_pct": round(100 * achieved / peak, 2) if peak else None,
        "achieved_tflops_per_chip": round(achieved, 1),
        "peak_tflops": peak,
        "step_time_ms": round(step_ms, 2),
        "step_time_ms_min": round(min(group_ms), 2),
        "step_time_ms_max": round(max(group_ms), 2),
    }


# --------------------------------------------------------- dispatch sweep


def _sweep_plan(cfg, n_steps: int):
    """Epoch-0 plan of the sweeps' tiny-MLP dataset (the probe harness
    consumes EpochPlans — the same input contract the train loop has)."""
    return data.plan_epoch(
        data.make_synthetic_data(n_steps * cfg.batch_size,
                                 cfg.data.n_features, cfg.data.seed),
        batch_size=cfg.batch_size, seed=cfg.seed, epoch=0)


def _dispatch_cell(cfg, mesh, k: int, n_steps: int, repeats: int) -> dict:
    """ms/step of the tiny-MLP train loop at superstep length k (k=1 =
    the per-step dispatch path, including its per-step put_batch — the
    real thing the superstep replaces). The compile/warmup/time-n-steps
    loop is tune.probe's — the sweep and the autotuner share one trial
    protocol, so BENCH_DISPATCH rows and probe trials are comparable."""
    from tpudist.tune import probe
    runner = probe.EpochRunner(cfg, mesh, k, _sweep_plan(cfg, n_steps),
                               n_steps)
    sampler = HbmSampler(period_s=0)   # manual sampling brackets the cell
    _, times, _ = probe.time_runner(runner, repeats=repeats)
    ms = statistics.median(times)
    return {"k": k, "step_ms": round(ms, 4),
            "steps_per_sec": round(1000 / ms, 1),
            **_sweep_obs_fields(runner.dispatch_fn, ms, sampler)}


def _staging_row(splan, superstep, budget_bytes, n_steps, ms,
                 sampler) -> dict:
    return {"mode": "streamed" if splan.streamed else "full_epoch",
            "budget_mb": (None if budget_bytes is None
                          else round(budget_bytes / 2**20, 4)),
            "slab_steps": splan.slab_steps, "n_slabs": splan.n_slabs,
            "epoch_mb": round(n_steps * splan.step_bytes / 2**20, 4),
            "superstep_compiles": len(superstep.traces),
            "step_ms": round(ms, 4),
            "steps_per_sec": round(1000 / ms, 1),
            **_sweep_obs_fields(superstep, ms, sampler)}


def run_staging_sweep(out_path: str, n_steps: int = 136,
                      repeats: int = 9) -> dict:
    """The staging-pipeline row: tiny-MLP steps/s at k=32 with full-epoch
    staging vs double-buffered streaming under a budget the epoch
    EXCEEDS by construction — the dataset that previously could not run
    (put_epoch staged the whole epoch or died) completes end-to-end.
    ``n_steps`` is deliberately not a k-multiple so both rows cross the
    zero-padded trailing partial slab; ``superstep_compiles`` must read
    1 in every row. The tracked artifact metric is the streamed/full
    steps/s ratio (the overlap claim: streaming should cost ~nothing)."""
    from tpudist.parallel import build_mesh
    from tpudist.tune import probe
    cfg = TrainConfig(batch_size=64, lr=1e-3, seed=0,
                      data=DataConfig(n_samples=n_steps * 64),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    k = 32
    plan = _sweep_plan(cfg, n_steps)
    batch_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    step_bytes = max(1, plan.bytes_per_step // batch_shards)
    # budget: exactly two k-step slabs + slack — a fraction of the epoch,
    # so the streamed row IS the previously-impossible over-budget run
    budget = int(2.5 * k * step_bytes)
    cells = [(None,), (budget,)]
    runners = {}
    for (b,) in cells:
        # tune.probe's epoch harness IS train._superstep_epoch's staging
        # shape (full-epoch fast path or double-buffered streaming)
        runner = probe.EpochRunner(cfg, mesh, k, plan, n_steps,
                                   budget_bytes=b)
        state = runner.init_state()
        state, loss = runner.run_epoch(state)  # trace + compile + warm
        jax.device_get(loss)
        # per-MODE sampler, created before this mode's timed epochs:
        # its peak brackets this mode's footprint, not the whole sweep
        runners[b] = [runner, state, runner.dispatch_fn, runner.splan, [],
                      HbmSampler(period_s=0)]
    # interleave the two modes' timed epochs so host-load drift affects
    # both equally instead of biasing whichever cell ran later
    for _ in range(repeats):
        for (b,) in cells:
            r = runners[b]
            t0 = time.perf_counter()
            r[1], loss = r[0].run_epoch(r[1])
            jax.device_get(loss)              # fence
            r[4].append((time.perf_counter() - t0) * 1000 / n_steps)
            r[5].sample()
    rows = [_staging_row(runners[b][3], runners[b][2], b, n_steps,
                         statistics.median(runners[b][4]), runners[b][5])
            for (b,) in cells]
    by_mode = {r["mode"]: r for r in rows}
    # ratio as the median of per-round ratios: each round's full and
    # streamed epochs run back-to-back, so load drift cancels pairwise
    # (per-mode medians across rounds would re-introduce it)
    ratio = round(statistics.median(
        f / s for f, s in zip(runners[None][4], runners[budget][4])), 4)
    art = {
        "metric": "staging_streamed_vs_full_steps_ratio",
        "value": ratio,
        "unit": "streamed steps/s / full-epoch steps/s (k=32)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "model": "mlp", "global_batch": cfg.batch_size,
            "k": k, "n_steps": n_steps,
            "rows": rows,
            "over_budget_dataset_completed": (
                by_mode["streamed"]["epoch_mb"]
                > by_mode["streamed"]["budget_mb"]),
            "one_compile_per_run": all(
                r["superstep_compiles"] == 1 for r in rows),
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art))
    return art


def run_memory_sweep(out_path: str, n_steps: int = 136) -> dict:
    """The memory-ledger row, BENCH_MEMORY.json: the HBM bucket bytes
    behind the two fixed-budget claims, computed from the SAME ledger
    arithmetic the train/serve lanes record (tpudist.obs.memledger) —
    (a) dense vs paged KV pool bytes for the serve lane's tiny
    transformer (pool + trash page + page table vs slots x max_seq),
    (b) full-epoch vs double-buffered streamed slab residency for the
    staging lane's over-budget tiny-MLP epoch (plan_slabs' own cut).
    Each row carries the ledger-derived columns (bucket bytes, headroom
    fraction, exactness) so the artifact states not just "paged is
    smaller" but how much device headroom each choice buys. Headline =
    paged/dense KV bucket byte ratio (< 1.0 is the claim)."""
    from tpudist.obs import memledger as memledger_lib
    from tpudist.parallel import build_mesh
    from tpudist.parallel import sharding as shd
    from tpudist.serve import kvcache
    from tpudist.serve.engine import init_params

    hbm = int(engine._device_hbm_bytes())
    rows = []

    def ledger_cols(led):
        return {"headroom_fraction": led["headroom_fraction"],
                "headroom_bytes": led["buckets"]["headroom"],
                "exact": led["exact"]}

    # (a) the serve lane's KV pair: same tiny transformer + pool shape
    # as run_serve_sweep's fixed-HBM pair, bytes from the specs' own
    # accounting (PagedCacheSpec.bytes includes trash page + table)
    model_cfg = ModelConfig(name="transformer", vocab_size=256,
                            n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=64)
    slots, max_seq, prompt_pad = 4, 64, 16
    mesh = build_mesh(ParallelConfig())
    params = init_params(model_cfg, mesh, seed=0)
    params_bytes = engine.state_bytes_per_device(params)
    dense_spec = kvcache.CacheSpec.from_model(
        model_cfg, slots=slots, max_seq=max_seq)
    paged_spec = kvcache.PagedCacheSpec.from_model(
        model_cfg, slots=2 * slots, max_seq=max_seq, page_tokens=8,
        pages=30)
    for mode, spec in (("dense", dense_spec), ("paged", paged_spec)):
        led = memledger_lib.build_ledger(
            total_hbm_bytes=hbm, params_bytes=params_bytes,
            kv_pool_bytes=spec.bytes, mode="serve")
        rows.append({"lane": "serve_kv", "mode": mode,
                     "slots": spec.slots,
                     "kv_pool_bytes": spec.bytes,
                     **ledger_cols(led)})
        print(json.dumps(rows[-1]))
    dense_kv, paged_kv = rows[0], rows[1]
    if paged_kv["kv_pool_bytes"] >= dense_kv["kv_pool_bytes"]:
        raise SystemExit(
            "memory sweep: paged KV bucket must be strictly smaller "
            f"than dense ({paged_kv['kv_pool_bytes']} vs "
            f"{dense_kv['kv_pool_bytes']} bytes)")

    # (b) the staging lane's slab pair: run_staging_sweep's over-budget
    # epoch shape, resident bytes from plan_slabs (x2 when streaming —
    # double-buffered) — no device work, this is the ledger's own math
    cfg = TrainConfig(batch_size=64, lr=1e-3, seed=0,
                      data=DataConfig(n_samples=n_steps * 64),
                      parallel=ParallelConfig(data=-1))
    k = 32
    plan = _sweep_plan(cfg, n_steps)
    batch_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    step_bytes = max(1, plan.bytes_per_step // batch_shards)
    budget = int(2.5 * k * step_bytes)
    state = engine.init_state(jax.random.PRNGKey(cfg.seed), cfg, mesh)
    st_params = engine.state_bytes_per_device(state.params)
    st_opt = engine.state_bytes_per_device(state.opt_state)
    for mode, b in (("full", None), ("streamed", budget)):
        splan = shd.plan_slabs(n_steps, k, step_bytes, b)
        resident = (min(2, splan.n_slabs) * splan.slab_bytes
                    if splan.streamed else splan.slab_bytes)
        led = memledger_lib.build_ledger(
            total_hbm_bytes=hbm, params_bytes=st_params,
            opt_state_bytes=st_opt, slab_bytes=resident, mode="train")
        rows.append({"lane": "staging_slabs", "mode": mode,
                     "budget_bytes": b, "n_slabs": splan.n_slabs,
                     "slab_resident_bytes": resident,
                     **ledger_cols(led)})
        print(json.dumps(rows[-1]))
    full_row, streamed_row = rows[2], rows[3]
    if streamed_row["slab_resident_bytes"] \
            >= full_row["slab_resident_bytes"]:
        raise SystemExit(
            "memory sweep: streamed slab residency must be strictly "
            "smaller than the full-epoch stage "
            f"({streamed_row['slab_resident_bytes']} vs "
            f"{full_row['slab_resident_bytes']} bytes)")

    art = {
        "metric": "paged_vs_dense_kv_bytes_ratio",
        "value": round(paged_kv["kv_pool_bytes"]
                       / dense_kv["kv_pool_bytes"], 4),
        "unit": "paged KV bucket bytes / dense KV bucket bytes "
                "(< 1.0 at 2x the slots)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "total_hbm_bytes": hbm,
            "rows": rows,
            "staging_resident_ratio": round(
                streamed_row["slab_resident_bytes"]
                / full_row["slab_resident_bytes"], 4),
            "headroom_gain_fraction_kv": round(
                paged_kv["headroom_fraction"]
                - dense_kv["headroom_fraction"], 6),
            "headroom_gain_fraction_staging": round(
                streamed_row["headroom_fraction"]
                - full_row["headroom_fraction"], 6),
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({key: art[key]
                      for key in ("metric", "value", "unit")}))
    return art


def run_dispatch_sweep(out_path: str, n_steps: int = 128,
                       repeats: int = 5) -> dict:
    """The dispatch-overhead row: steps/s on the tiny MLP at superstep
    k=1 vs 8 vs 32. The model is deliberately dispatch-bound (the paper's
    regime), so the k=1→32 delta IS the per-step dispatch+fence cost;
    ``dispatch_overhead_ms`` (ms/step at k=1 minus ms/step at k=32) is
    the tracked artifact metric for future PRs."""
    from tpudist.parallel import build_mesh
    cfg = TrainConfig(batch_size=64, lr=1e-3, seed=0,
                      data=DataConfig(n_samples=n_steps * 64),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    rows = [_dispatch_cell(cfg, mesh, k, n_steps, repeats)
            for k in (1, 8, 32)]
    by_k = {r["k"]: r for r in rows}
    art = {
        "metric": "dispatch_overhead_ms_per_step",
        "value": round(by_k[1]["step_ms"] - by_k[32]["step_ms"], 4),
        "unit": "ms/step (k=1 minus k=32)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "model": "mlp", "global_batch": cfg.batch_size,
            "rows": rows,
            "speedup_k32_vs_k1": round(
                by_k[32]["steps_per_sec"] / by_k[1]["steps_per_sec"], 3),
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art))
    return art


def run_tune_sweep(out_path: str, n_steps: int = 128,
                   repeats: int = 5) -> dict:
    """The autotuner row: heuristic-pick vs measured-probe steps/s on the
    CPU dispatch-bound tiny MLP, against the k={1,8,32} dispatch sweep as
    ground truth. ``--log-every 32`` shapes the legal k space to the full
    ladder {1..32}, so the search must climb the same curve the sweep
    measures; the artifact records whether the selected point lands
    within 10% of the sweep's best (the acceptance band) and that an
    immediate re-tune is a pure cache hit — zero probe trials."""
    import tempfile

    from tpudist import tune as tune_lib
    from tpudist.parallel import build_mesh
    cfg = TrainConfig(batch_size=64, lr=1e-3, seed=0, log_every=32,
                      autotune_cache_dir=tempfile.mkdtemp(
                          prefix="tpudist_tune_"),
                      data=DataConfig(n_samples=n_steps * 64),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    sweep = [_dispatch_cell(cfg, mesh, k, n_steps, repeats)
             for k in (1, 8, 32)]
    plan = _sweep_plan(cfg, n_steps)
    first = tune_lib.autotune(cfg, mesh, plan, mode="probe",
                              n_steps=n_steps, repeats=repeats)
    rerun = tune_lib.autotune(cfg, mesh, plan, mode="probe",
                              n_steps=n_steps, repeats=repeats)
    best_sps = max(r["steps_per_sec"] for r in sweep)
    sel_sps = first.steps_per_sec or 0.0
    art = {
        "metric": "autotuned_vs_heuristic_steps_ratio",
        "value": round(sel_sps / (first.baseline_steps_per_sec or sel_sps
                                  or 1.0), 4),
        "unit": "autotuned steps/s / heuristic-pick steps/s (tiny MLP)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "model": "mlp", "global_batch": cfg.batch_size,
            "n_steps": n_steps, "log_every": cfg.log_every,
            "sweep_rows": sweep,
            "selected": {**first.tuned.as_dict(),
                         "steps_per_sec": first.steps_per_sec},
            "heuristic_steps_per_sec": first.baseline_steps_per_sec,
            "tuning_status": first.status,
            "trials": first.trials, "pruned": first.pruned,
            "fingerprint": first.fingerprint,
            "within_10pct_of_sweep_best": bool(sel_sps >= 0.9 * best_sps),
            "rerun_source": rerun.source,
            "rerun_trials": rerun.trials,
            "rerun_is_pure_cache_hit": bool(
                rerun.source == "cache" and rerun.trials == 0),
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art))
    return art


# ------------------------------------------------------------- ckpt sweep


def run_ckpt_sweep(out_path: str, n_steps: int = 64, repeats: int = 4,
                   k: int = 8) -> dict:
    """The checkpoint-overhead curve: tiny-MLP steps/s at k=8 with an
    epoch-end save under each checkpoint mode — none (baseline),
    orbax-sync, orbax-async, and the elastic sharded-manifest writer
    (tpudist.elastic.ckpt) — on the shared tune.probe epoch harness, so
    the rows are directly comparable to BENCH_DISPATCH/BENCH_STAGING.
    Each row splits the save cost the honest way the Checkpointer does:
    ``enqueue_ms`` (what the train loop pays inline, snapshot+handoff),
    ``drain_ms`` (the blocked time at close that async modes defer), and
    the steps/s DIP vs the no-checkpoint baseline (save windows timed
    INSIDE the per-epoch wall, so hidden async cost stays hidden and
    exposed sync cost shows). The tracked artifact metric is the
    sharded-manifest dip — the price of preemption survival."""
    import shutil
    import tempfile

    from tpudist import checkpoint as ckpt_lib
    from tpudist.elastic import ckpt as elastic_ckpt
    from tpudist.parallel import build_mesh
    from tpudist.tune import probe

    cfg = TrainConfig(batch_size=64, lr=1e-3, seed=0,
                      data=DataConfig(n_samples=n_steps * 64),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    plan = _sweep_plan(cfg, n_steps)

    def make_ckpt(mode, d):
        if mode == "none":
            return None
        if mode == "sharded":
            return elastic_ckpt.ShardedCheckpointer(d, use_async=True)
        return ckpt_lib.Checkpointer(d, use_async=(mode == "orbax-async"))

    rows = []
    for mode in ("none", "orbax-sync", "orbax-async", "sharded"):
        d = tempfile.mkdtemp(prefix=f"tpudist_ckpt_{mode}_")
        runner = probe.EpochRunner(cfg, mesh, k, plan, n_steps)
        state = runner.init_state()
        state, loss = runner.run_epoch(state)    # trace + compile + warm
        jax.device_get(loss)
        ck = make_ckpt(mode, d)
        ms, enq = [], []
        for r in range(repeats):
            t0 = time.perf_counter()
            state, loss = runner.run_epoch(state)
            jax.device_get(loss)                 # fence
            if ck is not None:
                ck.save(state, epoch=r + 1, step_in_epoch=0)
                enq.append(ck.last_enqueue_ms)
            ms.append((time.perf_counter() - t0) * 1000 / n_steps)
        t0 = time.perf_counter()
        if ck is not None:
            ck.close()
        drain = (time.perf_counter() - t0) * 1000
        shutil.rmtree(d, ignore_errors=True)
        step_ms = statistics.median(ms)
        rows.append({
            "mode": mode, "step_ms": round(step_ms, 4),
            "steps_per_sec": round(1000 / step_ms, 1),
            "enqueue_ms_mean": (round(statistics.mean(enq), 2)
                                if enq else None),
            "enqueue_ms_max": round(max(enq), 2) if enq else None,
            "drain_ms": round(drain, 2) if ck is not None else None,
            "saves": len(enq)})
    base = rows[0]["steps_per_sec"]
    for r in rows:
        r["steps_dip_pct"] = round(100 * (1 - r["steps_per_sec"] / base), 2)
    by_mode = {r["mode"]: r for r in rows}
    art = {
        "metric": "ckpt_sharded_steps_dip_pct",
        "value": by_mode["sharded"]["steps_dip_pct"],
        "unit": "% steps/s lost to sharded-manifest epoch saves vs no "
                "checkpointing (tiny MLP, k=8)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "model": "mlp", "global_batch": cfg.batch_size,
            "k": k, "n_steps": n_steps, "saves_per_mode": repeats,
            "rows": rows,
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art))
    return art


# -------------------------------------------------------------- serve sweep


def run_serve_sweep(out_path: str, requests: int = 32,
                    max_new: int = 16, rate: float = 200.0) -> dict:
    """The serving row: decode-throughput curve over the decode_k ladder
    × KV layouts on the serve probe harness (tpudist.serve.tune — full
    occupancy, compiled superstep, same measurement the serve autotuner
    trusts), then ONE real continuous-batching run at the sweep's best
    point for the latency numbers only the request clock can produce:
    p50/p99 TTFT, inter-token latency, tokens/s/chip, and the SLO
    verdict. BENCH_SERVE.json on the shared artifact shape."""
    from tpudist.parallel import build_mesh
    from tpudist.serve import scheduler as sched
    from tpudist.serve import slo as slo_lib
    from tpudist.serve import tune as serve_tune
    from tpudist.serve.engine import (PagedServeEngine, ServeEngine,
                                      init_params)

    model_cfg = ModelConfig(name="transformer", vocab_size=256,
                            n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=64)
    slots, max_seq, prompt_pad = 4, 64, 16
    mesh = build_mesh(ParallelConfig())
    params = init_params(model_cfg, mesh, seed=0)

    rows = []
    for layout in ("st", "hs"):
        for k in (1, 8, 32):
            res = serve_tune.probe_candidate(
                model_cfg, mesh, params,
                serve_tune.ServeCandidate(decode_k=k, layout=layout),
                slots=slots, max_seq=max_seq, prompt_pad=prompt_pad)
            rows.append({"decode_k": k, "layout": layout,
                         "feasible": res.feasible,
                         "tokens_per_sec": round(res.tokens_per_sec, 2),
                         # inf dispatch_ms (pruned point) must not leak
                         # a bare `Infinity` token into the JSON
                         "dispatch_ms": (round(res.dispatch_ms, 4)
                                         if res.feasible else None),
                         "spread": round(res.spread, 4),
                         **({"error": res.error} if res.error else {})})
            print(json.dumps(rows[-1]))
    feasible = [r for r in rows if r["feasible"]]
    if not feasible:
        raise SystemExit(
            "serve sweep: every (decode_k, layout) point was infeasible "
            "on this device — see the per-point errors above; no "
            "BENCH_SERVE.json written")
    best = max(feasible, key=lambda r: r["tokens_per_sec"])

    engine = ServeEngine(model_cfg, mesh, slots=slots, max_seq=max_seq,
                         prompt_pad=prompt_pad,
                         decode_k=best["decode_k"],
                         layout=best["layout"])
    engine.warmup(params)
    reqs = sched.make_requests(requests, prompt_pad=prompt_pad,
                               vocab_size=model_cfg.vocab_size,
                               max_new=max_new, rate=rate, seed=0)
    summary = sched.run_serve(engine, params, reqs)
    engine.assert_two_programs()

    # Fixed-HBM dense-vs-paged pair: the tentpole's headline number.
    # Size the paged pool to STRICTLY FEWER KV bytes than the dense
    # cache (pool + trash page + page table vs slots×max_seq), then
    # drive both with the same shared-prefix load — the paged engine
    # must sustain strictly more concurrent sequences inside the
    # smaller footprint (one prefix page serves every slot; tails only
    # allocate pages they reach).
    pair_rows = []
    prefix_len, pair_reqs, pair_rate = 8, 24, 500.0
    # seed must match the pair stream below — the scheduler byte-checks
    # each prompt against the registered prefix before sharing pages
    shared = sched.shared_prefix_tokens(prefix_len,
                                        model_cfg.vocab_size, seed=1)
    for mode, eng in (
            ("dense", ServeEngine(
                model_cfg, mesh, slots=slots, max_seq=max_seq,
                prompt_pad=prompt_pad, decode_k=8, layout="st")),
            ("paged", PagedServeEngine(
                model_cfg, mesh, slots=2 * slots, max_seq=max_seq,
                prompt_pad=prompt_pad, decode_k=8, page_tokens=8,
                pages=30))):
        eng.warmup(params)
        rs = sched.make_requests(pair_reqs, prompt_pad=prompt_pad,
                                 vocab_size=model_cfg.vocab_size,
                                 max_new=max_new, rate=pair_rate,
                                 seed=1, prefix_len=prefix_len)
        summ = sched.run_serve(eng, params, rs, shared_prefix=shared)
        eng.assert_two_programs()
        pair_rows.append({
            "mode": mode, "slots": eng.slots,
            "kv_cache_bytes": eng.spec.bytes,
            "active_slots_peak": summ["active_slots_peak"],
            "completed": summ["completed"],
            "tokens_per_sec": summ["tokens_per_sec"],
            "kv_pages_used_peak": summ["kv_pages_used_peak"],
            "shared_prefix_len": summ["shared_prefix_len"]})
        print(json.dumps(pair_rows[-1]))
    dense_row, paged_row = pair_rows
    if paged_row["kv_cache_bytes"] >= dense_row["kv_cache_bytes"]:
        raise SystemExit(
            "serve sweep: paged KV footprint must be strictly smaller "
            f"than dense ({paged_row['kv_cache_bytes']} vs "
            f"{dense_row['kv_cache_bytes']} bytes)")
    if paged_row["active_slots_peak"] <= dense_row["active_slots_peak"]:
        raise SystemExit(
            "serve sweep: paged engine must sustain strictly more "
            "concurrent slots than dense at fixed HBM "
            f"({paged_row['active_slots_peak']} vs "
            f"{dense_row['active_slots_peak']})")

    art = {
        "metric": "serve_tokens_per_sec_per_chip",
        "value": summary["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip (continuous batching, greedy decode)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": jax.device_count(),
            "model": "transformer", "slots": slots,
            "max_seq": max_seq, "prompt_pad": prompt_pad,
            "request_rate": rate,
            "sweep_rows": rows,
            "selected": {"decode_k": best["decode_k"],
                         "layout": best["layout"]},
            **{k: summary.get(k) for k in (
                "requests", "completed", "generated_tokens",
                "truncated", "wall_s", "dispatches", "tokens_per_sec",
                "queue_depth_max", "queue_depth_mean", "ttft_p50_s",
                "ttft_p99_s", "itl_p50_s", "itl_p99_s", "e2e_p50_s",
                "e2e_p99_s", "prefill_compiles", "decode_compiles")},
            "kv_cache_bytes": engine.spec.bytes,
            "paged_pair": pair_rows,
        },
        "slo": slo_lib.slo_block(summary),
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({k: art[k] for k in ("metric", "value", "unit")}
                     | {"slo": art["slo"]["status"]}))
    return art


# ----------------------------------------------------------- overlap sweep


def _overlap_capture_exposed(run_once, tag: str) -> tuple:
    """``(exposed_comm_frac, exposed_comm_s)`` of ONE profiled epoch of
    ``run_once`` (obs.devtime's interval math over the jax.profiler
    capture — the SAME analysis the --profile-window train path grades
    with, so the bench's number and the run report's number are one
    measurement)."""
    import shutil
    import tempfile

    from tpudist.obs import devtime as devtime_lib
    cap = tempfile.mkdtemp(prefix=f"tpudist_ov_{tag}_")
    jax.profiler.start_trace(cap)
    run_once()
    jax.profiler.stop_trace()
    pod = devtime_lib.analyze_capture(cap)["pod"]
    shutil.rmtree(cap, ignore_errors=True)
    return pod["exposed_comm_frac"] or 0.0, pod["exposed_comm_s"]


def run_overlap_sweep(out_path: str, n_steps: int = 16, repeats: int = 2,
                      k: int = 1, rounds: int = 5) -> dict:
    """The overlap-plane artifact, BENCH_OVERLAP.json: (a) the DP
    gradient all-reduce schedule — barrier baseline vs bucketed overlap
    across bucket sizes, steps/s + devtime-measured exposed-comm
    fraction + BITWISE loss parity, on the 2-slice scripted DCN mapping
    (TPUDIST_SLICE_MAP, mesh.axis_fabric labels the data axis "dcn");
    (b) the pipeline schedule — GPipe vs interleaved-1F1B steps/s at
    S=4, M=8 with loss parity and the analytic bubble model per row.
    Headline = bucketed/barrier steps/s at the best bucketed point.

    Measurement honesty, hard-won: (1) both halves warm EVERY cell
    before timing any and interleave timed rounds across cells —
    sequential cell timing hands the first (baseline) cell the
    process's ~30% cold-start cost and manufactures phantom wins; (2)
    the DP half measures at k=1 (inside a k-step superstep scan the
    NEXT step's forward overlaps the trailing reduces in EITHER mode —
    a superstep property, not a schedule property; the superstep x
    overlap composition is pinned in tests/test_overlap.py); (3) on
    this CPU backend the two DP schedules then measure within noise —
    profiling serializes the overlapped concurrency (the capture
    cannot see what it grades) and the merged per-host track lets
    replica skew cover either schedule — so the DP rows are recorded
    diagnostics while the CI-asserted DP evidence is deterministic:
    bitwise loss parity + the lowered programs' barrier structure
    (detail.program), the property that stops the collective combiner
    re-fusing the reduces on the hardware backends where the wall win
    lives. The pipeline half IS a fair measured win (~1.1x at S=4,
    M=8)."""
    import dataclasses

    from tpudist.parallel import build_mesh
    from tpudist.parallel import mesh as mesh_lib
    from tpudist.tune import probe

    # the scripted 2-slice DCN stand-in: labeling only, program
    # unchanged (mesh.slice_assignment). Explicit env wins.
    os.environ.setdefault("TPUDIST_SLICE_MAP", "2")
    n_dev = jax.device_count()

    # ---- pipeline half: GPipe vs interleaved at S=4, M=8 ----
    # (runs FIRST: the DP half's runners hold several hundred MB of
    # state + staged epochs, and allocator pressure measurably drags
    # the pipeline cells when they run second)
    pp_rows = []
    if n_dev >= 4:
        # activation-heavy, param-light: the interleaved schedule's win
        # is the (S-1)(1-1/v) bubble slots of layer compute it removes,
        # while its cost is per-slot param traffic (chunk select + the
        # slot scan's carried layer-grad accumulation) — so tokens per
        # microbatch must dominate param bytes for the bubble cut to
        # show as wall clock on CPU (on TPU the same ratio comes free:
        # MXU compute dwarfs HBM param reads at real model sizes)
        pmodel = ModelConfig(name="transformer", vocab_size=128,
                             n_layers=8, d_model=128, n_heads=4,
                             n_kv_heads=4, d_ff=512, max_seq_len=64)
        S, M = 4, 8
        pcfg = TrainConfig(batch_size=32, lr=1e-3, seed=0, model=pmodel,
                           pp_microbatches=M,
                           data=DataConfig(n_samples=32),
                           parallel=ParallelConfig(data=1, pipe=S))
        pmesh = build_mesh(pcfg.parallel, devices=jax.devices()[:S])
        toks = data.make_synthetic_tokens(pcfg.batch_size,
                                          pmodel.max_seq_len + 1,
                                          pmodel.vocab_size, seed=0)
        from tpudist.parallel import sharding as shd
        pcells = {}
        # build + compile + warm BOTH schedules before timing either
        for v in (1, 2):
            cfg = dataclasses.replace(pcfg, pipeline_interleave=v)
            state = engine.init_state(jax.random.PRNGKey(0), cfg, pmesh)
            step = engine.make_train_step(cfg, pmesh)
            batch_t = shd.put_batch(pmesh, (toks,))
            for _ in range(2):
                state, loss = step(state, batch_t)
            jax.device_get(loss)
            # parity pin: one fresh-step loss per schedule
            fstate = engine.init_state(jax.random.PRNGKey(0), cfg, pmesh)
            _, floss = step(fstate, batch_t)
            pcells[v] = [step, state, batch_t, [],
                         float(jax.device_get(floss))]
        # timed rounds interleaved across the two schedules
        for _ in range(max(repeats, 3)):
            for v, c in pcells.items():
                t0 = time.perf_counter()
                for _ in range(4):
                    c[1], loss = c[0](c[1], c[2])
                jax.device_get(loss)
                c[3].append((time.perf_counter() - t0) * 1000 / 4)
        for v, c in pcells.items():
            ms = statistics.median(c[3])
            pp_rows.append({
                "schedule": "gpipe" if v == 1 else "interleaved",
                "interleave": v, "stages": S, "microbatches": M,
                "bubble_model": round((S - 1) / (v * M + S - 1), 4),
                "step_ms": round(ms, 4),
                "steps_per_sec": round(1000 / ms, 2),
                "first_step_loss": c[4]})
            print(json.dumps(pp_rows[-1]))
        del pcells

    # ---- DP half: param-heavy little transformer, pure-DP mesh ----
    # Shape chosen comm-forward (wide layers, short sequences, 1 row
    # per device): the gradient all-reduce must be a visible fraction
    # of the device window (~10% exposed at the barrier baseline here)
    # or the schedule comparison measures profiler noise. ~21 MB of
    # f32 grads over 8 stacked-layer leaves + embed.
    model = ModelConfig(name="transformer", vocab_size=256, n_layers=8,
                        d_model=384, n_heads=4, n_kv_heads=4, d_ff=768,
                        max_seq_len=16)
    base = TrainConfig(batch_size=n_dev, lr=1e-3, seed=0,
                       model=model,
                       data=DataConfig(n_samples=n_steps * n_dev),
                       parallel=ParallelConfig(data=-1))
    mesh = build_mesh(base.parallel)
    fabric = mesh_lib.data_fabric(mesh)
    plan = data.plan_epoch(
        (data.make_synthetic_tokens(base.batch_size * n_steps,
                                    model.max_seq_len + 1,
                                    model.vocab_size, base.data.seed),),
        batch_size=base.batch_size, seed=base.seed, epoch=0)

    cells = [("off", None)] + [("bucketed", mb) for mb in (1.0, 4.0)]
    runners = {}
    # phase 1 — build, compile, warm EVERY cell before any timing:
    # the process's first epochs run cold (allocator growth, code
    # caches — tune.probe's documented ~30% first-trial bias), and the
    # baseline cell measuring first would wear all of it
    for mode, mb in cells:
        cfg = dataclasses.replace(base, grad_overlap=mode,
                                  grad_bucket_mb=mb)
        runner = probe.EpochRunner(cfg, mesh, k, plan, n_steps)
        state = runner.init_state()
        state, loss = runner.run_epoch(state)   # trace + compile + warm
        jax.device_get(loss)
        # a fresh state for the parity pin: every cell's first-epoch
        # loss from the identical init must agree BITWISE (the overlap
        # modes are schedule-only — parallel.overlap)
        pstate = runner.init_state()
        pstate, ploss = runner.run_epoch(pstate)
        # ploss is the last superstep's per-step loss vector (n_steps is
        # a k-multiple here, so its last entry is a real step's loss)
        loss_bits = float(jax.device_get(ploss).ravel()[-1])
        runners[(mode, mb)] = [runner, state, [], loss_bits, []]
    # phase 2 — timed epochs INTERLEAVED across cells (the staging
    # sweep's drift-cancelling discipline): each round times every cell
    # back-to-back so host-load drift hits all modes of a round equally
    # instead of biasing whichever cell ran later
    for _ in range(max(repeats, 3)):
        for key in runners:
            r = runners[key]
            t0 = time.perf_counter()
            s, loss = r[0].run_epoch(r[1])
            jax.device_get(loss)
            r[1] = s
            r[2].append((time.perf_counter() - t0) * 1000 / n_steps)
    # phase 3 — capture rounds, same interleaving
    for _ in range(rounds):
        for key in runners:
            r = runners[key]

            def once(r=r):
                s, loss = r[0].run_epoch(r[1])   # donates the state
                r[1] = s
                jax.device_get(loss)
            r[4].append(_overlap_capture_exposed(
                once, f"{key[0]}_{key[1]}"))
    rows = []
    for (mode, mb), (runner, _, times, loss_bits, caps) in \
            runners.items():
        ms = statistics.median(times)
        traces = getattr(runner.dispatch_fn, "traces", None)
        fracs = [c[0] for c in caps]
        # captured exposure rides the rows as a labeled DIAGNOSTIC, not
        # the headline: profiling the CPU thunk runtime serializes the
        # very concurrency the bucketed schedule buys (measured: the
        # bucketed cell's captured window runs at the barrier cell's
        # pace while its un-profiled step time is ~1.3x faster), so
        # under the profiler the two schedules read alike. The honest
        # CPU-measurable signal is the un-profiled wall clock below;
        # per-device TPU tracks don't share the observer effect.
        per_step_ms = [1e3 * c[1] / n_steps for c in caps]
        rows.append({"mode": mode, "grad_bucket_mb": mb,
                     "fabric": fabric,
                     "step_ms": round(ms, 4),
                     "steps_per_sec": round(1000 / ms, 1),
                     "superstep_compiles": (len(traces)
                                            if traces is not None
                                            else None),
                     "first_epoch_loss": loss_bits,
                     "exposed_comm_frac": round(
                         statistics.median(fracs), 5),
                     "exposed_comm_frac_reps": [round(f, 5)
                                                for f in fracs],
                     "exposed_comm_ms_per_step": round(
                         statistics.median(per_step_ms), 4),
                     "exposed_comm_ms_per_step_reps": [
                         round(x, 4) for x in per_step_ms]})
        print(json.dumps(rows[-1]))
    off_row = rows[0]
    best = max(rows[1:], key=lambda r: r["steps_per_sec"])
    reduction = round(best["steps_per_sec"] / off_row["steps_per_sec"],
                      4)

    # the DETERMINISTIC schedule evidence (what CPU wall clock cannot
    # adjudicate — fair interleaved timing measures the two schedules
    # within ±3% here, sign unstable): the lowered programs must carry
    # the structure the modes promise — off barriers every grad leaf
    # once; bucketed threads one barrier per chain link, which is what
    # stops the collective combiner re-fusing the reduces into the
    # trailing all-reduce on hardware backends
    def _lowered_text(cfg):
        from jax.sharding import PartitionSpec as P

        from tpudist.parallel import sharding as shd
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        body, _, _ = engine._build_step_body(cfg, mesh)

        def jitted(st, batch):
            bspecs = jax.tree.map(
                lambda x: shd.batch_spec(x.ndim), batch)
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), bspecs),
                                 out_specs=(P(), P()),
                                 check_vma=False)(st, batch)
        batch = jax.tree.map(lambda a: a[0], plan.slab(0, 1))
        staged = shd.put_batch(mesh, batch)
        return jax.jit(jitted).lower(state, staged).as_text()

    def _barrier_count(mode, mb):
        return _lowered_text(dataclasses.replace(
            base, grad_overlap=mode,
            grad_bucket_mb=mb)).count("optimization_barrier")
    program = {
        "off_barriers": _barrier_count("off", None),
        "bucketed_barrier_chain": _barrier_count(
            "bucketed", best["grad_bucket_mb"]),
    }

    # ---- cross-slice half: flat vs hierarchical per slice count ----
    # Same honesty discipline as the DP half (warm all cells, then
    # interleave timed rounds), and the same division of labor: steps/s
    # rides as a no-regression diagnostic (on CPU both schedules run the
    # same reduction work; the hierarchical win is DCN byte volume, which
    # only hardware wall clock can convert to time) while the asserted
    # evidence is program-derived — per-step DCN bytes from the lowered
    # StableHLO must shrink by exactly the slice size.
    from tpudist.obs import devtime as devtime_lib
    xs_rows = []
    xs_cells = {}
    slice_counts = [s for s in (2, 4, 8)
                    if s <= n_dev and n_dev % s == 0]
    for n_slices in slice_counts:
        os.environ["TPUDIST_SLICE_MAP"] = str(n_slices)
        device_slices = mesh_lib.mesh_device_slices(mesh)
        for cross in ("flat", "hierarchical"):
            cfg = dataclasses.replace(base, grad_overlap="bucketed",
                                      grad_bucket_mb=4.0,
                                      cross_slice=cross)
            runner = probe.EpochRunner(cfg, mesh, k, plan, n_steps)
            state = runner.init_state()
            # compile + warm; the warm epoch runs from fresh init, so
            # its loss doubles as the parity value
            state, loss = runner.run_epoch(state)
            loss_bits = float(jax.device_get(loss).ravel()[-1])
            coll = devtime_lib.collective_bytes(_lowered_text(cfg),
                                                device_slices)
            print(json.dumps({"cell": [n_slices, cross],
                              "first_epoch_loss": loss_bits,
                              "dcn_bytes_per_step":
                                  coll["dcn_bytes_total"]}))
            xs_cells[(n_slices, cross)] = [runner, state, [], loss_bits,
                                           coll]
    os.environ["TPUDIST_SLICE_MAP"] = "2"   # the sweep's scripted map
    for _ in range(max(repeats, 3)):
        for key in xs_cells:
            r = xs_cells[key]
            t0 = time.perf_counter()
            s, loss = r[0].run_epoch(r[1])
            jax.device_get(loss)
            r[1] = s
            r[2].append((time.perf_counter() - t0) * 1000 / n_steps)
    for (n_slices, cross), (_, _, times, loss_bits, coll) in \
            xs_cells.items():
        ms = statistics.median(times)
        xs_rows.append({
            "n_slices": n_slices, "slice_size": n_dev // n_slices,
            "cross_slice": cross,
            "step_ms": round(ms, 4),
            "steps_per_sec": round(1000 / ms, 1),
            "first_epoch_loss": loss_bits,
            "dcn_bytes_per_step": coll["dcn_bytes_total"],
            "ici_bytes_per_step": coll["ici_bytes_total"],
            "n_collectives": coll["n_collectives"]})
        print(json.dumps(xs_rows[-1]))
    for n_slices in slice_counts:
        flat_r = next(r for r in xs_rows
                      if r["n_slices"] == n_slices
                      and r["cross_slice"] == "flat")
        hier_r = next(r for r in xs_rows
                      if r["n_slices"] == n_slices
                      and r["cross_slice"] == "hierarchical")
        slice_size = n_dev // n_slices
        if flat_r["first_epoch_loss"] != hier_r["first_epoch_loss"]:
            raise SystemExit(
                "overlap sweep: hierarchical loss must match flat "
                f"bitwise at {n_slices} slices "
                f"({hier_r['first_epoch_loss']} vs "
                f"{flat_r['first_epoch_loss']})")
        ratio = flat_r["dcn_bytes_per_step"] / hier_r["dcn_bytes_per_step"]
        # exact when slice_size divides every bucket's element count
        # (it does for this model); the loss all-reduce's 4-byte payload
        # rides both sides, hence the sliver of tolerance
        if slice_size > 1 and abs(ratio - slice_size) > 0.02 * slice_size:
            raise SystemExit(
                "overlap sweep: hierarchical DCN bytes must be "
                f"flat/slice_size at {n_slices} slices (ratio {ratio:.4f}"
                f" vs slice_size {slice_size})")
        if hier_r["steps_per_sec"] < 0.7 * flat_r["steps_per_sec"]:
            raise SystemExit(
                "overlap sweep: hierarchical steps/s regressed beyond "
                f"the CPU noise floor at {n_slices} slices "
                f"({hier_r['steps_per_sec']} vs "
                f"{flat_r['steps_per_sec']})")

    art = {
        "metric": "grad_overlap_steps_ratio",
        "value": reduction,
        "unit": "bucketed steps/s / barrier-baseline steps/s at "
                "bitwise-identical loss (4-dev CPU mesh, scripted "
                "2-slice DCN map; captured exposure rides the rows)",
        "detail": {
            "device": jax.devices()[0].device_kind,
            "n_devices": n_dev,
            "model": "transformer", "global_batch": base.batch_size,
            "k": k, "n_steps": n_steps,
            "slice_map": os.environ.get("TPUDIST_SLICE_MAP"),
            "data_axis_fabric": fabric,
            "rows": rows,
            "best_bucket_mb": best["grad_bucket_mb"],
            "program": program,
            "exposed_comm_frac_drop": round(
                off_row["exposed_comm_frac"]
                - best["exposed_comm_frac"], 5),
            "loss_bitwise_identical": all(
                r["first_epoch_loss"] == off_row["first_epoch_loss"]
                for r in rows),
            "one_compile_per_cell": all(
                r["superstep_compiles"] in (None, 1) for r in rows),
            "steps_ratio_best_vs_off": round(
                best["steps_per_sec"] / off_row["steps_per_sec"], 4),
            "cross_slice_rows": xs_rows,
            "cross_slice_loss_bitwise_identical": all(
                r["first_epoch_loss"] == off_row["first_epoch_loss"]
                for r in xs_rows),
            "pipeline_rows": pp_rows,
            **({"pipeline_interleaved_vs_gpipe_steps_ratio": round(
                    pp_rows[1]["steps_per_sec"]
                    / pp_rows[0]["steps_per_sec"], 4),
                "pipeline_loss_bitwise_identical": (
                    pp_rows[0]["first_step_loss"]
                    == pp_rows[1]["first_step_loss"])}
               if len(pp_rows) == 2 else {}),
        },
    }
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({k_: art[k_] for k_ in ("metric", "value", "unit")}))
    return art


# --------------------------------------------------------- collective sweep


def run_collective_sweep(out_path: str, kinds: str, min_mb: float,
                         max_mb: float, iters: int) -> dict:
    """Promote the collective sweep to a first-class artifact:
    BENCH_COLLECTIVES.json (an original BASELINE.json north-star
    artifact that never existed) — per-kind per-size bus GB/s and % of
    ring peak, each axis labeled ICI vs DCN from the mesh, on the same
    harness shape as the other BENCH_* files. ``tpudist.bench.sweep``
    does the measuring (and stays the launcher's GATE); this wrapper
    only shapes and writes the artifact, so the two never drift."""
    from tpudist.bench import sweep as sweep_mod
    records = sweep_mod.run_sweep(tuple(kinds.split(",")), "data",
                                  min_mb=min_mb, max_mb=max_mb,
                                  iters=iters)
    if jax.process_index() == 0:
        art = sweep_mod.write_collectives_artifact(records, out_path)
    else:
        art = sweep_mod.collectives_artifact(records)
    print(json.dumps({k: art[k] for k in ("metric", "value", "unit")}))
    return art


# ------------------------------------------------------------- chaos drill


def run_chaos_drill(out_path: str) -> dict:
    """The recovery-under-fault headline: drive the seeded fault matrix
    (tpudist.chaos — seven families, policy → requeue → resume against
    the real CLI) and write BENCH_CHAOS.json on the BENCH_* harness
    shape. The measurement half is the invariant checker's report: how
    many families ended green, with per-family resume/goodput facts in
    the detail block. The drill driver is jax-free; only its
    subprocesses touch devices, so this wrapper stays a thin shaper
    like the collective sweep's (chaos.verify owns the orchestration
    and the artifact shape — one source for the CLI, this flag and
    selfcheck)."""
    from tpudist.chaos import verify as chaos_verify

    art = chaos_verify.bench_artifact(chaos_verify.run_and_verify())
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({k: art[k] for k in ("metric", "value", "unit")}))
    return art


# ----------------------------------------------------- serve chaos drill


def run_serve_chaos_drill(out_path: str) -> dict:
    """The serve-resilience headline: drive the scripted overload +
    serve fault matrix (tpudist.serve.drill — bounded-queue shedding
    with the arrival partition checked exactly, serve_kill → policy →
    requeue → resume with in-flight slots honestly lost, garbage
    rejection, straggler stall, adapt ladder) and write
    BENCH_SERVE_RESILIENCE.json on the BENCH_* harness shape. The
    measurement half is the jax-free verifier's report: how many
    scenarios ended green, with per-scenario shed/resume facts in the
    detail block. A thin shaper like run_chaos_drill — serve.drill
    owns the orchestration and artifact shape (one source for its CLI,
    this flag and selfcheck check_serve_resilience)."""
    from tpudist.serve import drill as serve_drill

    art = serve_drill.bench_artifact(serve_drill.run_and_verify())
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({k: art[k] for k in ("metric", "value", "unit")}))
    return art


# ------------------------------------------------------------------ matrix

# (model, seq, head, flash, per_chip[, remat]) — meaningful cells only:
#   * per-chip batch keeps tokens/step ≈ 28k as seq grows (the measured
#     plain-path plateau), 96 for the fused head (its reason to exist),
#     24 for no-flash at 512 (dense scores OOM above).
#   * chunked head (c4) rows cover the remaining LM-head strategy.
#   * no-flash rows measure the XLA fallback (dense at 512, blockwise at
#     2048/4096) — the CPU-test reference path's on-chip cost.
#   * one MoE row (8 experts, top-2, same backbone) at the dense plateau
#     batch; group size pre-tuned via --moe-group.
MATRIX_ROWS = [
    ("transformer", 512, "plain", True, 56, False),
    ("transformer", 512, "fused", True, 96, True),
    ("transformer", 512, "c4", True, 56, False),
    ("transformer", 512, "plain", False, 24, False),
    ("transformer", 2048, "plain", True, 12, False),
    ("transformer", 2048, "c4", True, 12, False),
    ("transformer", 2048, "plain", False, 12, False),
    ("transformer", 4096, "plain", True, 6, False),
    ("transformer", 4096, "c4", True, 6, False),
    ("transformer", 4096, "plain", False, 6, False),
    ("transformer", 8192, "plain", True, 3, False),
    # long-context frontier, batch 1-2 with the chunked head. No remat at
    # 16k: activations fit one v5e and remat cost 9 MFU points (41.2% vs
    # 50.1% measured r4)
    ("transformer", 16384, "c8", True, 2, False),
    ("transformer", 32768, "c16", True, 1, False),
    # 64/chip: the GQA plateau sits higher than dense's 56 (the compact
    # kv projections free HBM) — r5 measured 56→106.0k, 64→107.2k
    # (80.6% MFU), 72→102.6k (remat pressure returns)
    ("gqa", 512, "plain", True, 64, False),
    # compact-kv advantage grows with seq: 4x fewer kv-proj FLOPs and
    # kv-block ring/DMA bytes — beats dense at every matched seq
    ("gqa", 2048, "plain", True, 12, False),
    ("gqa", 4096, "plain", True, 6, False),
    ("gqa", 8192, "plain", True, 3, False),
    ("moe", 512, "plain", True, 32, False),
    ("moe", 512, "fused", True, 32, True),
    # r5 additions: the fused premium isolated at the plain row's batch
    # (no remat, no batch confound), and MoE coverage past seq 512
    ("transformer", 512, "fused", True, 56, False),
    ("moe", 2048, "plain", True, 8, False),
    ("moe_gqa", 512, "plain", True, 32, False),
    ("moe_cf1", 512, "plain", True, 32, False),
]


def run_cell(spec: str, iters: int, moe_group: int) -> None:
    """One matrix cell (subprocess entry): prints exactly one JSON line."""
    model, seq, head, flash, per_chip, remat = spec.split(":")
    seq, per_chip = int(seq), int(per_chip)
    flash, remat = flash == "1", remat == "1"
    label = (f"{model}/seq{seq}/{head}/"
             f"{'flash' if flash else 'noflash'}/b{per_chip}")
    base = {"config": label, "model": model, "seq": seq, "lm_head": head,
            "flash": flash, "remat": remat}
    try:
        cfg = build_cfg(seq=seq, per_chip=per_chip, head=head,
                        model=model, remat=remat, moe_group=moe_group)
        rec = {**base, **measure(cfg, iters=iters)}
    except Exception as e:   # OOM/compile failure is a result, not a crash
        rec = {**base, "error": f"{type(e).__name__}: {str(e)[:200]}"}
    print("MATRIX_CELL " + json.dumps(rec), flush=True)


def run_matrix(iters: int, out_path: str, moe_group: int) -> dict:
    """Each cell runs in a fresh subprocess: (a) a cell's OOM/compile crash
    cannot kill the sweep, and (b) env that must differ per cell
    (TPUDIST_NO_FLASH; the scoped-VMEM workaround below) is snapshotted at
    first PJRT use, so it cannot be changed within one process."""
    import subprocess
    import sys
    here = os.path.abspath(__file__)
    rows = []
    for model, seq, head, flash, per_chip, remat in MATRIX_ROWS:
        spec = (f"{model}:{seq}:{head}:{int(flash)}:{per_chip}:{int(remat)}")
        env = dict(os.environ)
        if flash:
            # an inherited escape-hatch var would silently bench the XLA
            # fallback under a "flash" label in the committed artifact
            env.pop("TPUDIST_NO_FLASH", None)
        else:
            env["TPUDIST_NO_FLASH"] = "1"
        rec = None
        try:
            r = subprocess.run(
                [sys.executable, here, "--cell", spec, "--iters",
                 str(iters), "--moe-group", str(moe_group)],
                env=env, capture_output=True, text=True, timeout=3000)
            for ln in r.stdout.splitlines():
                if ln.startswith("MATRIX_CELL "):
                    rec = json.loads(ln[len("MATRIX_CELL "):])
            tail = f"rc={r.returncode}: {(r.stderr or r.stdout)[-200:]}"
        except subprocess.TimeoutExpired:
            # a wedged cell must not lose the rows already measured
            tail = "timeout after 3000s"
        if rec is None:
            rec = {"config": spec, "model": model, "seq": seq,
                   "lm_head": head, "flash": flash, "remat": remat,
                   "error": f"cell subprocess {tail}"}
        print(json.dumps(rec), flush=True)
        rows.append(rec)
    art = {"matrix_version": 1, "rows": rows}
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(markdown_table(rows))
    return art


def markdown_table(rows) -> str:
    """README-ready table, regenerated from the artifact (single source)."""
    lines = ["| model | seq | LM head | attention | batch/chip | tok/s/chip "
             "| MFU % | step ms |",
             "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        att = "flash" if r.get("flash") else "XLA fallback"
        if "error" in r:
            # raw error text contains newlines/'|' that break the table
            err = " ".join(r["error"].split()).replace("|", "/")[:40]
            lines.append(f"| {r['model']} | {r['seq']} | {r['lm_head']} | "
                         f"{att} | — | — | — | {err} |")
            continue
        lines.append(
            f"| {r['model']} | {r['seq']} | {r['lm_head']} | {att} | "
            f"{r['global_batch'] // r['n_devices']} | {r['tok_s_chip']:,} | "
            f"{r['mfu_pct']} | {r['step_time_ms']} |")
    return "\n".join(lines)


def main() -> None:
    from tpudist.utils import enable_compilation_cache, tune_tpu
    tune_tpu()
    enable_compilation_cache()

    p = argparse.ArgumentParser()
    p.add_argument("--fused-xent", action="store_true",
                   help="bench the pallas fused LM-head variant")
    p.add_argument("--batch-per-chip", type=int, default=None)
    p.add_argument("--iters", type=int, default=60)
    p.add_argument("--matrix", action="store_true",
                   help="bench the full perf surface; write BENCH_MATRIX.json")
    p.add_argument("--dispatch-sweep", action="store_true",
                   help="bench superstep dispatch overhead (tiny MLP, "
                        "k=1/8/32); write BENCH_DISPATCH.json")
    p.add_argument("--dispatch-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DISPATCH.json"))
    p.add_argument("--staging-sweep", action="store_true",
                   help="bench full-epoch vs streamed double-buffered "
                        "staging (tiny MLP, k=32, over-budget dataset); "
                        "write BENCH_STAGING.json")
    p.add_argument("--staging-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_STAGING.json"))
    p.add_argument("--memory-sweep", action="store_true",
                   help="compute the HBM ledger's bucket bytes for "
                        "dense-vs-paged KV and full-vs-streamed slab "
                        "residency (tpudist.obs.memledger arithmetic, "
                        "ledger-derived headroom columns); write "
                        "BENCH_MEMORY.json")
    p.add_argument("--memory-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_MEMORY.json"))
    p.add_argument("--tune-sweep", action="store_true",
                   help="bench the measured-probe autotuner against the "
                        "dispatch sweep (heuristic-pick vs autotuned "
                        "steps/s, cache re-hit); write BENCH_TUNE.json")
    p.add_argument("--tune-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_TUNE.json"))
    p.add_argument("--ckpt-sweep", action="store_true",
                   help="bench checkpoint save overhead (none vs "
                        "orbax-sync vs orbax-async vs elastic sharded "
                        "manifest): enqueue/drain ms + steps/s dip; "
                        "write BENCH_CKPT.json")
    p.add_argument("--ckpt-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_CKPT.json"))
    p.add_argument("--serve-sweep", action="store_true",
                   help="bench the serving engine: decode_k × KV-layout "
                        "throughput curve on the serve probe harness + "
                        "one continuous-batching run at the best point "
                        "(TTFT/ITL percentiles, SLO verdict); write "
                        "BENCH_SERVE.json")
    p.add_argument("--serve-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_SERVE.json"))
    p.add_argument("--overlap-sweep", action="store_true",
                   help="bench the overlap plane: DP gradient "
                        "all-reduce barrier-vs-bucketed (steps/s + "
                        "devtime exposed-comm frac across bucket "
                        "sizes, bitwise loss parity, scripted 2-slice "
                        "DCN labels) and GPipe-vs-interleaved pipeline "
                        "steps/s at S=4, M=8; write BENCH_OVERLAP.json")
    p.add_argument("--overlap-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_OVERLAP.json"))
    p.add_argument("--collective-sweep", action="store_true",
                   help="sweep the collectives over the mesh's data "
                        "axis (ICI/DCN-labeled) and write "
                        "BENCH_COLLECTIVES.json — per-kind per-size bus "
                        "GB/s + %% of ring peak")
    p.add_argument("--collective-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_COLLECTIVES.json"))
    p.add_argument("--collective-kinds", type=str,
                   default="all_reduce,all_gather,reduce_scatter,"
                           "all_to_all,ppermute")
    p.add_argument("--collective-min-mb", type=float, default=1)
    p.add_argument("--collective-max-mb", type=float, default=1024)
    p.add_argument("--collective-iters", type=int, default=10)
    p.add_argument("--chaos-drill", action="store_true",
                   help="run the seeded fault-injection matrix "
                        "(tpudist.chaos: kill/hang/slow/corrupt/torn/"
                        "fs-error/telemetry-garbage against the real "
                        "CLI) and write BENCH_CHAOS.json — headline = "
                        "fault families ending green")
    p.add_argument("--chaos-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_CHAOS.json"))
    p.add_argument("--serve-chaos-drill", action="store_true",
                   help="run the serve resilience matrix "
                        "(tpudist.serve.drill: 2x-overload shedding "
                        "with exact partition + bitwise determinism, "
                        "serve_kill->requeue->resume, request_garbage "
                        "rejection, serve_slow, adapt ladder) and "
                        "write BENCH_SERVE_RESILIENCE.json — headline "
                        "= resilience scenarios ending green")
    p.add_argument("--serve-chaos-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_SERVE_RESILIENCE.json"))
    p.add_argument("--cell", type=str, default=None,
                   help="internal: run one matrix cell "
                        "(model:seq:head:flash:per_chip:remat)")
    p.add_argument("--matrix-out", type=str, default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_MATRIX.json"))
    p.add_argument("--moe-group", type=int, default=256,
                   help="MoE routing group size for the matrix's moe rows "
                        "(dispatch einsum FLOPs scale linearly with it; "
                        "256 = r4 measured optimum on v5e)")
    args = p.parse_args()

    if args.cell:
        run_cell(args.cell, args.iters, args.moe_group)
        return
    if args.dispatch_sweep:
        run_dispatch_sweep(args.dispatch_out)
        return
    if args.staging_sweep:
        run_staging_sweep(args.staging_out)
        return
    if args.memory_sweep:
        run_memory_sweep(args.memory_out)
        return
    if args.tune_sweep:
        run_tune_sweep(args.tune_out)
        return
    if args.ckpt_sweep:
        run_ckpt_sweep(args.ckpt_out)
        return
    if args.serve_sweep:
        run_serve_sweep(args.serve_out)
        return
    if args.overlap_sweep:
        run_overlap_sweep(args.overlap_out)
        return
    if args.collective_sweep:
        run_collective_sweep(args.collective_out, args.collective_kinds,
                             args.collective_min_mb,
                             args.collective_max_mb,
                             args.collective_iters)
        return
    if args.chaos_drill:
        run_chaos_drill(args.chaos_out)
        return
    if args.serve_chaos_drill:
        run_serve_chaos_drill(args.serve_chaos_out)
        return
    if args.matrix:
        run_matrix(max(20, args.iters // 2), args.matrix_out, args.moe_group)
        return

    # 56/chip: measured plateau on v5e for the plain path with the
    # round-3 kernels (single-block flash specialisation, merged dq/dk/dv
    # backward, custom xent VJP): 40→93.5k, 48→95.4k, 52→95.9k, 56→96.2k,
    # 60→94.7k, 64→91.5k tok/s/chip. Beyond 56 XLA's rematerialisation
    # (driven by the f32 logits pair the plain head materialises) grows
    # faster than the batch amortisation — measured 31 ms/step of .remat
    # fusions at 56, and every explicit alternative (chunked head, fused
    # kernel, whole-layer remat) benched slower. The fused head removes
    # the logits tensor from HBM so it runs big-batch; pairing it with
    # remat keeps the backbone activations within HBM at batch 96.
    # with TPUDIST_NO_FLASH the dense-attention path peaks ~85k (48/chip).
    no_flash = bool(os.environ.get("TPUDIST_NO_FLASH"))
    per_chip = args.batch_per_chip or (
        96 if args.fused_xent else (24 if no_flash else 56))
    cfg = build_cfg(seq=512, per_chip=per_chip,
                    head="fused" if args.fused_xent else "plain",
                    remat=args.fused_xent)
    m = measure(cfg, iters=args.iters)

    prior = best_prior_bench()
    tok_s_chip = m["tok_s_chip"]
    print(json.dumps({
        "metric": "transformer_train_tokens_per_sec_per_chip",
        "value": tok_s_chip,
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_s_chip / prior, 4) if prior else 1.0,
        "detail": {
            **{k: m[k] for k in (
                "device", "n_devices", "global_batch", "seq_len", "n_params",
                "mfu_pct", "achieved_tflops_per_chip", "peak_tflops",
                "step_time_ms", "step_time_ms_min", "step_time_ms_max")},
            "lm_head": "fused_xent" if args.fused_xent else "plain",
            "steps_per_sec_per_chip": round(
                1000 / m["step_time_ms"] / m["n_devices"], 4),
            "prior_best_tok_s_chip": prior,
        },
    }))


if __name__ == "__main__":
    main()
