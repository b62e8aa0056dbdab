"""On-chip kernel self-check: hardware truth as an acceptance gate.

The reference gates publishing on the distributed job succeeding on real
hardware (reference distributed-gpu-test-ci.yaml:222); its only test body
is the training job itself. tpudist additionally ships Mosaic-compiled
pallas kernels whose correctness the CPU test lane can only check in the
interpreter — a kernel regression that manifests only under the real
Mosaic compiler (layout, VMEM, padding-row hazards) would otherwise reach
production silently. This module is the launcher's pre-training gate: it
re-derives the load-bearing checks of ``tests_tpu/`` without pytest (the
workload image carries none), prints one PASS/FAIL line per check, and
exits nonzero on any failure — which the launcher turns into a ``fail``
verdict before training even starts.

Run:  python3 -m tpudist.selfcheck          (on a TPU host)
      python3 -m tpudist.selfcheck --allow-cpu   (interpreted, for dev)
"""

from __future__ import annotations

import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np


from tpudist.ops.reference import dense_attention as _ref_attn
from tpudist.ops.reference import lm_head_xent as _ref_xent


def _xent_data(t, d, v, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (t, d), dtype),
            jax.random.normal(k2, (v, d), dtype) * 0.02,
            jax.random.randint(k3, (t,), 0, v))


def _check_fused_xent_shape(t: int, v: int):
    """One hazard shape of the fused LM-head xent vs the reference —
    forward and both grads. The grad atol scales with 1/t: the mean loss
    makes dh entries O(1/t), so a FIXED atol goes vacuous at large t
    (r4 review: max|dh| ≈ 5e-6 at t=20000 vs the old atol 1e-5 — a
    broken second partial chunk would have passed); large entries stay
    pinned by rtol either way. Shared by the pytest lane
    (tests_tpu/test_tpu_lane.py) so the two lanes cannot drift."""
    from tpudist.ops.pallas.fused_xent import fused_lm_head_xent
    h, emb, tgt = _xent_data(t, 256, v)
    got = float(fused_lm_head_xent(h, emb, tgt))
    want = float(_ref_xent(h, emb, tgt))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               err_msg=f"fwd t={t} v={v}")
    g_got = jax.grad(lambda h, e: fused_lm_head_xent(h, e, tgt),
                     argnums=(0, 1))(h, emb)
    g_want = jax.grad(_ref_xent, argnums=(0, 1))(h, emb, tgt)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=5e-3 / t,
                                   err_msg=f"grad t={t} v={v}")


def check_fused_xent():
    """Fused LM-head xent vs the reference at the interpreter-hidden
    hazard shapes: aligned, token remainder (the r1 dE padded-row bug),
    vocab remainder, and t=20000 — 10 token supergroups at the default
    block_t_bwd=2048, i.e. TWO outer partial-chunk kernel calls (the
    _MAX_PARTIALS cap) plus a masked supergroup remainder, compiled
    (r4: the merged backward's dE-partials accumulation path)."""
    for t, v in ((512, 4096), (400, 4096), (512, 5000), (20000, 4096)):
        _check_fused_xent_shape(t, v)


def check_fused_xent_bench_geometry():
    """Bench geometry (d=2048, vocab 32000, bf16, default blocks) must fit
    VMEM in fwd and both backward kernels and produce finite grads."""
    from tpudist.ops.pallas.fused_xent import fused_lm_head_xent
    h, emb, tgt = _xent_data(1024, 2048, 32000, dtype=jnp.bfloat16)
    loss, (gh, ge) = jax.value_and_grad(
        lambda h, e: fused_lm_head_xent(h, e, tgt), argnums=(0, 1))(h, emb)
    np.testing.assert_allclose(float(loss), float(_ref_xent(h, emb, tgt)),
                               rtol=5e-2)
    assert bool(jnp.isfinite(gh.astype(jnp.float32)).all()), "dh not finite"
    assert bool(jnp.isfinite(ge.astype(jnp.float32)).all()), "dE not finite"


def _check_flash(kv: int):
    """Mosaic flash attention vs dense XLA at bench head geometry, bf16,
    causal — fwd + all three grads; kv=2 covers GQA group-sum on chip."""
    from tpudist.ops.pallas.flash_attention import flash_attention
    b, s, h, hd = 4, 512, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.bfloat16)

    dense = _ref_attn

    got = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    want = jax.jit(dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
    g_got = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        flash_attention(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        dense(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(g_got, g_want, "q k v".split()):
        # bf16 operands, values O(30): elementwise ULP-scale differences
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=0.5,
                                   err_msg=f"d{name}")


def check_flash_attention():
    _check_flash(kv=8)


def check_flash_attention_gqa():
    _check_flash(kv=2)


def _check_flash_long(kv: int):
    """The MULTI-block schedule (seq 2048 = 4 kv blocks): online-softmax
    rescale, accumulator revisits, causal block skipping — a disjoint
    Mosaic code path from the single-block specialisation the seq-512
    checks compile. kv < h additionally compiles the in-kernel GQA
    _expand_rep/_group_sum under the accumulator schedule (r3 advisor:
    flash is the default at all sequence lengths, so a GQA model at seq
    ≥ 1024 hits this path with no other on-chip coverage). Compared
    against the blockwise XLA decomposition."""
    from tpudist.ops.blockwise_attention import blockwise_causal_attention
    from tpudist.ops.pallas.flash_attention import flash_attention
    b, s, h, hd = 1, 2048, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.bfloat16)
    got = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    want = jax.jit(lambda q, k, v: blockwise_causal_attention(
        q, k, v))(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
    g_got = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        flash_attention(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        blockwise_causal_attention(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(g_got, g_want, "q k v".split()):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=0.5,
                                   err_msg=f"d{name}")


def check_flash_attention_long_context():
    _check_flash_long(kv=4)


def check_flash_attention_gqa_long_context():
    _check_flash_long(kv=2)


def check_ring_flash_merge():
    """The ring-attention hop merge on chip: two disjoint-kv kernel calls
    merged with merge_partials (lse = logaddexp, o = Σ exp(lse_i − lse)·o_i)
    must equal one whole-kv kernel call — forward AND gradients (the dlse
    cotangent folding into the kernels' delta constant). This is exactly
    the per-hop operation of ops.ring_attention's flash path, minus the
    ppermute (one chip has no ring); the multichip dryrun exercises the
    full ring on a virtual mesh."""
    from tpudist.ops.pallas.flash_attention import flash_attention_with_lse
    from tpudist.ops.ring_attention import merge_partials
    b, s, h, hd = 2, 1024, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, 2, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, 2, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.bfloat16)
    c = s // 2

    def whole(q, k, v):
        o, _ = flash_attention_with_lse(q, k, v, causal=False)
        return o.astype(jnp.float32)

    def merged(q, k, v):
        o1, l1 = flash_attention_with_lse(q, k[:, :c], v[:, :c],
                                          causal=False)
        o2, l2 = flash_attention_with_lse(q, k[:, c:], v[:, c:],
                                          causal=False)
        o, _ = merge_partials(o1.astype(jnp.float32), l1,
                              o2.astype(jnp.float32), l2)
        return o

    got = jax.jit(merged)(q, k, v)
    want = jax.jit(whole)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2)
    g_got = jax.jit(jax.grad(lambda a, b_, c_: jnp.vdot(
        merged(a, b_, c_), ct), argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda a, b_, c_: jnp.vdot(
        whole(a, b_, c_), ct), argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(g_got, g_want, "q k v".split()):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=0.5,
                                   err_msg=f"d{name}")


def _train_smoke(model_kw):
    from tpudist import data as tdata
    from tpudist import engine
    from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                                TrainConfig)
    from tpudist.parallel import build_mesh
    # batch scales with the slice so the data axis always divides it —
    # on a pod this smoke is a real all-chip DP train step
    batch = max(8, jax.device_count())
    cfg = TrainConfig(
        batch_size=batch, lr=1e-3, seed=0, dtype="bfloat16",
        data=DataConfig(n_samples=batch), model=ModelConfig(**model_kw),
        parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = tdata.make_synthetic_tokens(batch, 65, 512, seed=0)
    state, l0 = step(state, (toks,))
    state, l1 = step(state, (toks,))
    l0, l1 = float(l0), float(l1)
    assert np.isfinite(l0) and np.isfinite(l1), f"loss not finite: {l0} {l1}"
    assert l1 < l0, f"loss did not decrease: {l0} -> {l1}"


def check_staging_stream():
    """The streaming input pipeline on chip: a tiny-MLP epoch run through
    double-buffered slab staging (budget forcing 3 slabs + a padded
    trailing partial superstep) must produce the SAME per-step losses as
    full-epoch staging, on one compiled superstep each — and the check
    reports the staged-bytes peak and overlap fraction the way a pod run
    would (train's ``tpudist: staging ...`` line / kind=timing record),
    so H2D that fails to hide behind compute is visible here too."""
    import time as _t

    import jax.numpy as jnp

    from tpudist import data as tdata
    from tpudist import engine, verdict
    from tpudist.config import DataConfig, ParallelConfig, TrainConfig
    from tpudist.metrics import StagingStats
    from tpudist.parallel import build_mesh
    from tpudist.parallel import sharding as shd

    batch = max(8, jax.device_count())
    n_steps, k = 10, 4
    cfg = TrainConfig(batch_size=batch, lr=1e-3, seed=0,
                      data=DataConfig(n_samples=n_steps * batch),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    plan = tdata.plan_epoch(
        tdata.make_synthetic_data(n_steps * batch, cfg.data.n_features,
                                  cfg.data.seed),
        batch_size=batch, seed=cfg.seed, epoch=0)
    batch_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    step_bytes = max(1, plan.bytes_per_step // batch_shards)

    def run(budget, stats):
        splan = shd.plan_slabs(n_steps, k, step_bytes, budget)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        superstep = engine.make_superstep(cfg, mesh, k)
        total = jnp.zeros((), jnp.float32)
        losses = []
        S = splan.slab_steps
        stats.streamed = splan.streamed

        def stage(s):
            t0 = _t.perf_counter()
            start, stop = s * S, min(n_steps, s * S + S)
            pad_to = -(-(stop - start) // k) * k
            arrs = shd.put_epoch(mesh, plan.slab(start, stop,
                                                 pad_to=pad_to))
            stats.note_staged(pad_to * step_bytes,
                              _t.perf_counter() - t0)
            return arrs, pad_to * step_bytes

        nxt = stage(0)
        for s in range(splan.n_slabs):
            cur, cur_bytes = nxt
            if s + 1 < splan.n_slabs:
                nxt = stage(s + 1)
            if s > 0:
                stats.note_wait(cur)
            base = s * S
            staged_len = jax.tree.leaves(cur)[0].shape[0]
            last = None
            for j in range(staged_len // k):
                gstart = base + j * k
                if gstart >= n_steps:
                    break
                hi = min(n_steps - gstart, k)
                slab = (cur if staged_len == k else
                        jax.tree.map(lambda a: a[j * k:(j + 1) * k], cur))
                state, total, ls = superstep(state, total, slab, 0, hi)
                last = ls
                losses.extend(np.asarray(ls)[:hi])
            if s + 1 < splan.n_slabs and last is not None:
                jax.device_get(last)       # slab-boundary fence
            stats.note_released(cur_bytes)
        assert len(superstep.traces) == 1, \
            f"superstep recompiled: {len(superstep.traces)} traces"
        return np.asarray(losses), float(total)

    t0 = _t.perf_counter()
    stream_stats = StagingStats()
    got = run(2 * k * step_bytes, stream_stats)       # 3 slabs, padded tail
    run_s = _t.perf_counter() - t0
    want = run(None, StagingStats())                  # full-epoch fast path
    np.testing.assert_array_equal(got[0], want[0])
    assert got[1] == want[1], (got[1], want[1])
    overlap = stream_stats.overlap_fraction(run_s)
    status = verdict.staging_status(stream_stats.streamed, overlap)
    print(f"  staging: {status}, peak {stream_stats.peak_bytes} B staged "
          f"over {stream_stats.slabs} slabs, overlap "
          f"{overlap if overlap is None else round(overlap, 3)}",
          flush=True)


def check_autotune():
    """The autotune search contract on a SCRIPTED probe harness (fake
    timers — no device work, so the drill runs identically on every
    backend): (a) an HBM-infeasible point is PRUNED — the search routes
    around it and still commits the best feasible point, instead of
    crashing or committing into an OOM; (b) when every explored point
    measures slower than the seed heuristic, the commit IS the seed
    heuristic — the tuner can only ever match or beat the static
    resolve_* guess it replaced."""
    from tpudist.tune import probe, search

    start = search.Candidate(k=8, staging_budget_mb=None, remat=False,
                             grad_accum_steps=1)
    axes = {"k": [1, 2, 4, 8, 16, 32], "staging_budget_mb": [None],
            "remat": [False], "grad_accum_steps": [1]}

    def scripted(sps_by_k, infeasible_ks=()):
        calls = []

        def measure(cand):
            calls.append(cand)
            if cand.k in infeasible_ks:
                return probe.ProbeResult(
                    0.0, float("inf"), 8, 1, feasible=False,
                    error="RESOURCE_EXHAUSTED (scripted hbm wall)")
            ms = 1000.0 / sps_by_k[cand.k]
            return probe.ProbeResult(sps_by_k[cand.k], ms, 8, 1)
        return measure, calls

    # (a) the fastest point on the curve (k=32) is over the fake HBM
    # wall: prune it, commit the best feasible point (k=16)
    measure, calls = scripted({1: 100.0, 2: 180.0, 4: 300.0, 8: 500.0,
                               16: 640.0}, infeasible_ks=(32,))
    out = search.coordinate_search(start, axes, measure, trial_budget=16)
    assert out.best.k == 16, f"expected k=16 commit, got {out.best}"
    assert out.pruned == 1, f"infeasible point not pruned: {out.pruned}"
    assert out.best_sps >= out.baseline_sps
    assert out.trials <= 16

    # (b) every alternative regresses the seed heuristic: the commit
    # must be the seed, exactly
    measure, calls = scripted({k: (500.0 if k == 8 else 200.0)
                               for k in (1, 2, 4, 8, 16, 32)})
    out2 = search.coordinate_search(start, axes, measure, trial_budget=16)
    assert out2.best == start, f"regressing commit: {out2.best}"
    assert out2.best_sps == out2.baseline_sps == 500.0

    # (c) a measure() that RAISES is a pruned point, not a dead search
    def exploding(cand):
        if cand.k == 32:
            raise RuntimeError("scripted probe crash")
        sps = {1: 100.0, 2: 180.0, 4: 300.0, 8: 500.0, 16: 640.0}[cand.k]
        return probe.ProbeResult(sps, 1000.0 / sps, 8, 1)
    out3 = search.coordinate_search(start, axes, exploding,
                                    trial_budget=16)
    assert out3.best.k == 16 and out3.pruned == 1, out3
    print(f"  autotune drill: hbm-wall commit k={out.best.k} "
          f"({out.trials} trials, {out.pruned} pruned), "
          f"regression floor held at k={out2.best.k}", flush=True)


def check_devtime():
    """The device-time attribution math on a SCRIPTED trace fixture
    (pure interval arithmetic — no device work, identical on every
    backend): known compute/comm intervals must yield the EXACT
    exposed-communication answer through every overlap edge case —
    comm nested inside compute (fully hidden), back-to-back comm
    windows whose union partially escapes compute, a lone comm burst
    with no compute at all (fully exposed) — and the
    compute/exposed/idle fractions must decompose the window exactly."""
    from tpudist.obs import devtime

    # classification: the names XLA actually emits
    assert devtime.classify("fusion.123") == "compute"
    assert devtime.classify("all-reduce.3") == "comm"
    assert devtime.classify("all-gather-start") == "comm"
    assert devtime.classify("ThunkExecutor::Execute") is None
    assert devtime.classify("$builtins isinstance") is None

    # scripted track (times in µs):
    #   compute  [0,10] [20,30]
    #   comm     [5,12]+[12,14] back-to-back -> exposed [10,14] = 4
    #            [25,30] nested in compute    -> fully hidden, 0
    #            [40,45] no compute anywhere  -> fully exposed, 5
    ops = [(0.0, 10.0, "fusion.1"), (20.0, 30.0, "dot.2"),
           (5.0, 12.0, "all-reduce.0"), (12.0, 14.0, "all-gather.0"),
           (25.0, 30.0, "all-reduce.1"),
           (40.0, 45.0, "collective-permute.0")]
    out = devtime.attribute_tracks({"dev0": ops})
    d = out["devices"]["dev0"]
    assert abs(d["exposed_comm_s"] * 1e6 - 9.0) < 1e-9, d
    assert abs(d["compute_s"] * 1e6 - 20.0) < 1e-9, d
    assert abs(d["comm_s"] * 1e6 - 19.0) < 1e-9, d
    # window [0,45]: busy = [0,14]+[20,30]+[40,45] = 29 -> idle 16
    assert abs(d["idle_s"] * 1e6 - 16.0) < 1e-9, d
    s = d["compute_frac"] + d["exposed_comm_frac"] + d["idle_frac"]
    assert abs(s - 1.0) < 1e-9, s
    # the verdict: 9/45 = 20% exposed clears the default 25% gate but
    # not a 10% one; no measurement is ungateable, not a pass
    assert devtime.comm_status(d["exposed_comm_frac"]) == "success"
    assert devtime.comm_status(d["exposed_comm_frac"], 0.10) == "fail"
    assert devtime.comm_status(None) == "ungateable"
    print(f"  devtime drill: exposed {d['exposed_comm_s'] * 1e6:.0f} µs "
          f"of {d['comm_s'] * 1e6:.0f} µs comm "
          f"({100 * d['exposed_comm_frac']:.1f}% of the window)",
          flush=True)


def check_elastic():
    """The preemption-survival contract on a scripted drill (host-side
    file + sharding machinery — no collectives, so it runs identically
    on one CPU host and on every pod worker): (a) a sharded-manifest
    save commits atomically and restores BITWISE onto the same mesh and
    onto a RESHAPED one (half the devices — the N→M slice-assembly
    reshard); (b) a scripted kill between the shard write and the
    commit leaves the PREVIOUS manifest authoritative — never a torn
    checkpoint — and the orphaned step directory is reaped on the next
    open; (c) the requeue policy classifies preemption/stall as
    requeue-able and a deterministic crash as stop."""
    import os
    import tempfile

    from tpudist import engine
    from tpudist.config import DataConfig, ParallelConfig, TrainConfig
    from tpudist.elastic import ckpt as eck
    from tpudist.elastic import policy
    from tpudist.elastic import resume as eres
    from tpudist.parallel import build_mesh

    # LOCAL devices only: on a pod every worker drills its own slice of
    # the machinery in its own temp dir (the drill's checkpointer runs
    # as its own single-process coordinator — a cross-host sharded save
    # would need a shared filesystem the selfcheck cannot assume)
    devs = jax.local_devices()
    nd = len(devs)
    cfg = TrainConfig(batch_size=32, data=DataConfig(n_samples=64),
                      parallel=ParallelConfig(
                          data=1, fsdp=nd if nd > 1 else 1))
    mesh = build_mesh(cfg.parallel, devices=devs)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    d = tempfile.mkdtemp(prefix="tpudist_elastic_")

    # (a) commit + same-mesh bitwise restore + reshard restore
    ck = eck.ShardedCheckpointer(d, use_async=False, run_meta={"seed": 0})
    ck.save(state, epoch=1, step_in_epoch=4)
    man = eck.latest_manifest(d)
    assert man is not None and (man["epoch"], man["step_in_epoch"]) == \
        (1, 4), man
    got, e, s = eres.restore(d, state, run_meta={"seed": 0})
    assert (e, s) == (1, 4)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, got)
    if nd > 1:
        half = TrainConfig(batch_size=32, data=DataConfig(n_samples=64),
                           parallel=ParallelConfig(data=1, fsdp=nd // 2))
        hmesh = build_mesh(half.parallel, devices=devs[:nd // 2])
        tmpl = engine.init_state(jax.random.PRNGKey(7), half, hmesh)
        resh, _, _ = eres.restore(d, tmpl, run_meta={"seed": 0})
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state, resh)

    # (b) kill between shard write and commit: previous manifest stays
    class _KilledBeforeCommit(eck.ShardedCheckpointer):
        def _commit(self, *a, **kw):
            pass                         # the scripted kill point

    state2 = engine.init_state(jax.random.PRNGKey(1), cfg, mesh)
    state2 = state2._replace(step=state2.step + 100)
    torn = _KilledBeforeCommit(d, use_async=False, run_meta={"seed": 0})
    torn.save(state2, epoch=9, step_in_epoch=0)
    man2 = eck.latest_manifest(d)
    assert int(man2["step"]) == int(man["step"]), \
        "uncommitted shards must not move the manifest"
    orphan = eck.step_dir(eck.elastic_root(d), 100)
    assert os.path.isdir(orphan), "drill setup: orphan dir should exist"
    removed = eck.cleanup_stale(d)
    assert orphan in removed and not os.path.isdir(orphan), \
        "stale uncommitted step dir must be reaped on the next open"
    got3, e3, s3 = eres.restore(d, state, run_meta={"seed": 0})
    assert (e3, s3) == (1, 4), "restore must still read the committed step"

    # (c) the requeue policy: signal deaths and stalls requeue (with
    # exponential backoff), deterministic crashes stop
    assert policy.decide(137, attempt=0, max_requeues=3).requeue
    assert policy.decide(124, attempt=1, max_requeues=3).backoff_s == 20.0
    assert not policy.decide(1, attempt=0, max_requeues=3).requeue
    assert not policy.decide(137, attempt=3, max_requeues=3).requeue
    print(f"  elastic drill: manifest step {man['step']} survived a "
          f"kill-before-commit, reshard onto {max(nd // 2, 1)} device(s) "
          f"bitwise, policy verdicts held", flush=True)


def check_chaos():
    """The seeded fault matrix end to end (tpudist.chaos): the REAL
    train CLI is driven in subprocesses on a 4-device CPU mesh under
    each of the seven fault families — hard kill, watchdog-tripping
    hang, slow-host straggler, checkpoint-shard corruption, torn
    manifest, transient filesystem errors, garbage on the live
    telemetry stream — replaying the launcher's own loop (fault →
    jax-free policy classification → backoff → ``--resume auto``), and
    the jax-free invariant checker replays the artifacts: the policy
    classified every fault correctly, resume came back from the newest
    COMMITTED step (bitwise vs the unfaulted baseline, by shard-index
    crc32 — the corrupted-shard family specifically falls back past
    its crc-rejected manifest), the goodput partition stayed exact
    with the lost steps counted, and every fail verdict had its
    matching mid-run alert. Writes into $TPUDIST_CHAOS_DRILL_DIR when
    set (CI uploads the artifacts), else a temp dir."""
    from tpudist.chaos import drill as chaos_drill
    from tpudist.chaos import verify as chaos_verify

    report = chaos_verify.run_and_verify()
    bad = {name: fam["problems"]
           for name, fam in report["families"].items() if not fam["ok"]}
    assert not bad, f"chaos invariants violated: {bad}"
    assert len(report["families"]) == len(chaos_drill.FAMILIES)
    print(f"  chaos matrix: {len(report['families'])} fault families "
          f"green (policy/resume/goodput/alert invariants held; "
          f"report in {report['run_dir']})", flush=True)


def check_serve_resilience():
    """The serve resilience plane end to end (tpudist.serve.drill): the
    REAL serve CLI is driven in subprocesses on a 4-device CPU mesh
    under scripted 2x overload and the serve-surface chaos families —
    bounded-queue shedding + deadline expiry with the arrival partition
    checked EXACTLY, a serve_kill at a dispatch boundary classified by
    the jax-free requeue policy and resumed with the dead attempt's
    in-flight slots honestly counted lost, seeded malformed requests
    rejected at admission, a per-dispatch straggler stall visible in
    the deterministic ITL, and sustained pressure downshifting the
    pre-compiled decode_k ladder without a recompile. The virtual
    clock makes two same-seed runs bitwise identical, and the jax-free
    verifier replays every invariant from the artifacts alone. Writes
    into $TPUDIST_SERVE_DRILL_DIR when set (CI uploads it), else a
    temp dir."""
    from tpudist.serve import drill as serve_drill

    report = serve_drill.run_and_verify()
    bad = {name: sc["problems"]
           for name, sc in report["scenarios"].items() if not sc["ok"]}
    assert not bad, f"serve resilience invariants violated: {bad}"
    assert len(report["scenarios"]) == len(serve_drill.SCENARIOS)
    print(f"  serve resilience: {len(report['scenarios'])} scenarios "
          f"green (shed partition exact, TTFT bounded under 2x "
          f"overload, kill->requeue->resume honest, report in "
          f"{report['run_dir']})", flush=True)


def check_flight_recorder():
    """The flight-recorder pipeline end-to-end with a DELIBERATELY
    wedged step: progress beacons flow while steps advance, then the
    'step' blocks past the stall window (the single-host stand-in for a
    worker stuck in a collective) and the watchdog must dump a
    flight-record artifact — containing thread stacks with the wedged
    frame, per-device memory stats, and the last progress/metrics —
    BEFORE the launcher's outer timeout would kill the job. Writes into
    $TPUDIST_OBS_DIR when set (CI uploads the artifacts), else a temp
    dir."""
    import json
    import os
    import tempfile
    import time as _t

    from tpudist.metrics import MetricsLogger
    from tpudist.obs import FlightRecorder

    out_dir = os.environ.get("TPUDIST_OBS_DIR") or tempfile.mkdtemp(
        prefix="tpudist_obs_")
    stall_s = 0.5
    metrics = MetricsLogger(path=os.path.join(out_dir, "metrics.jsonl"))
    rec = FlightRecorder(out_dir, stall_timeout_s=stall_s,
                         process_index=jax.process_index(),
                         metrics=metrics)
    try:
        for step in range(3):            # healthy steps: beacon advances
            rec.note_progress(phase="train", epoch=0, step=step)
            metrics.log(kind="step", step=step, loss=1.0 / (step + 1))
            _t.sleep(0.05)
        assert rec.dumps == 0, "watchdog fired on a healthy run"

        def wedged_step():               # the hang: no progress notes
            deadline = _t.monotonic() + 20 * stall_s
            while rec.dumps == 0 and _t.monotonic() < deadline:
                _t.sleep(0.05)
        wedged_step()
        assert rec.dumps >= 1, "watchdog never fired on the wedged step"
        # the stall dump itself must have flushed the buffered metrics
        # (crash safety) — asserted BEFORE close(), whose flush would
        # otherwise mask a missing dump-time flush
        with open(os.path.join(out_dir, "metrics.jsonl")) as f:
            assert len(f.readlines()) >= 3, \
                "stall dump did not flush metrics"
    finally:
        rec.close()
        metrics.close()

    with open(rec.flightrec_path) as f:
        art = json.load(f)               # must parse: CI asserts this too
    assert art["reason"] == "stall", art["reason"]
    assert art["progress"]["step"] == 2 and art["progress"]["phase"] == \
        "train", art["progress"]
    assert "wedged_step" in art["thread_stacks"], \
        "stall dump missing the wedged frame"
    assert isinstance(art["memory_stats"], list)
    assert art["last_metrics"] and art["last_metrics"][-1]["step"] == 2
    with open(rec.beacon_path) as f:
        beacon = json.load(f)
    assert beacon["step"] == 2
    print(f"  flight record: {rec.flightrec_path} "
          f"({len(art['thread_stacks'])} B of stacks)", flush=True)


def check_live():
    """The live-telemetry stall path end-to-end over REAL sockets: a
    worker whose step loop wedges must get its ``stall`` alert onto the
    Prometheus exporter and into ``live_status.json`` BEFORE any
    launcher kill — the single-host stand-in for the pod stall story
    (emitter → TCP ingest → aggregator → alert engine → /metrics, the
    same path a pod exercises). Writes into $TPUDIST_OBS_DIR when set
    (CI uploads the artifacts), else a temp dir."""
    import json
    import os
    import tempfile
    import time as _t
    import urllib.request

    from tpudist.metrics import MetricsLogger
    from tpudist.obs import FlightRecorder
    from tpudist.obs import live as live_mod

    out_dir = os.environ.get("TPUDIST_OBS_DIR") or tempfile.mkdtemp(
        prefix="tpudist_live_")
    stall_s = 0.4
    live = live_mod.LiveRun.start(
        is_coordinator=True, process_index=0, out_dir=out_dir,
        run_id="live-drill", stall_timeout_s=stall_s)
    metrics = MetricsLogger(path=os.path.join(out_dir, "metrics.jsonl"))
    metrics.emitter = live.emitter
    rec = FlightRecorder(
        out_dir, stall_timeout_s=stall_s, process_index=0,
        metrics=metrics, emitter=live.emitter,
        extra_state=lambda: {"live_status": live.snapshot_fields()})
    try:
        for step in range(3):            # healthy steps: beacons flow
            rec.note_progress(phase="train", epoch=0, step=step)
            metrics.log(kind="step", step=step, loss=1.0 / (step + 1))
            _t.sleep(0.05)

        deadline = _t.monotonic() + 30 * stall_s   # the wedge
        while rec.dumps == 0 and _t.monotonic() < deadline:
            _t.sleep(0.05)
        assert rec.dumps >= 1, "watchdog never fired on the wedged step"

        # the firing alert must reach the EXPORTER while the process is
        # still alive (i.e. before any launcher kill) — bounded wait for
        # the emitter→TCP→aggregator hop, then a real HTTP scrape
        deadline = _t.monotonic() + 5.0
        while _t.monotonic() < deadline:
            if any(a["alert"] == "stall"
                   for a in live.aggregator.engine.firing()):
                break
            _t.sleep(0.05)
        url = f"http://127.0.0.1:{live.exporter.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as r:
            text = r.read().decode()
        assert 'tpudist_alert_firing{alert="stall"} 1' in text, \
            "stall alert not scrapeable at /metrics"
        with open(os.path.join(out_dir, "live_status.json")) as f:
            status = json.load(f)
        assert status["status"] == "alert", status["status"]
        assert any(a["alert"] == "stall"
                   for a in status["alerts"]["firing"]), status["alerts"]
    finally:
        rec.close()
        live.close()
        metrics.close()

    with open(rec.flightrec_path) as f:
        art = json.load(f)
    assert "live_status" in (art.get("extra") or {}), \
        "pre-kill flight record missing the aggregator's live snapshot"
    print(f"  live drill: stall alert scrapeable at :{live.exporter.port}"
          f"/metrics before the kill; {out_dir}/live_status.json = "
          f"{status['status']}", flush=True)


def check_train_step_smoke():
    """One bf16 train step of the tiny transformer: finite, decreasing."""
    _train_smoke(dict(name="transformer", vocab_size=512, n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
                      max_seq_len=64))


def check_moe_smoke():
    """MoE dispatch einsums + expert FFN compile and train on the chip."""
    _train_smoke(dict(name="moe", vocab_size=512, n_layers=2, d_model=128,
                      n_heads=4, n_kv_heads=4, d_ff=128, max_seq_len=64,
                      n_experts=4, expert_top_k=2))


CHECKS = [
    check_autotune,
    check_chaos,
    check_devtime,
    check_elastic,
    check_fused_xent,
    check_fused_xent_bench_geometry,
    check_flash_attention,
    check_flash_attention_gqa,
    check_flash_attention_long_context,
    check_flash_attention_gqa_long_context,
    check_ring_flash_merge,
    check_staging_stream,
    check_flight_recorder,
    check_live,
    check_serve_resilience,
    check_train_step_smoke,
    check_moe_smoke,
]


def main(argv=None) -> int:
    from tpudist.utils import enable_compilation_cache, tune_tpu
    tune_tpu()
    enable_compilation_cache()
    # Multi-host slices: every worker runs this (libtpu on a pod worker
    # cannot initialize standalone — a lone process hangs waiting for the
    # rest of the slice). The checks themselves are host-local jits; with
    # distributed init they run replicated, one copy per worker, and any
    # worker's failure fails its ssh command (srun semantics). No-op on a
    # single host.
    from tpudist.parallel import distributed
    distributed.initialize()
    argv = list(sys.argv[1:] if argv is None else argv)
    allow_cpu = "--allow-cpu" in argv
    backend = jax.default_backend()
    if backend != "tpu" and not allow_cpu:
        # this lane exists to be hardware truth: silently interpreting the
        # kernels on CPU would pass while the Mosaic path is broken
        print(f"selfcheck: backend is {backend!r}, not tpu — refusing "
              f"(pass --allow-cpu to run interpreted for development)")
        return 2
    checks = CHECKS
    if "--only" in argv:
        # run a single named check (CI's forced-stall flight-recorder
        # drill uses this; an unknown or missing name is an error, not
        # an empty green run)
        idx = argv.index("--only") + 1
        name = argv[idx] if idx < len(argv) else None
        checks = [fn for fn in CHECKS if fn.__name__ == name]
        if not checks:
            print(f"selfcheck: no check named {name!r} "
                  f"(have: {', '.join(fn.__name__ for fn in CHECKS)})")
            return 2
    failed = 0
    for fn in checks:
        t0 = time.perf_counter()
        try:
            fn()
            print(f"PASS {fn.__name__} ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
        except Exception:
            failed += 1
            print(f"FAIL {fn.__name__}", flush=True)
            traceback.print_exc()
    n = len(checks)
    print(f"selfcheck: {n - failed}/{n} passed", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
