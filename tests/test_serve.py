"""tpudist.serve: the batched inference engine's acceptance pins.

The two correctness anchors the ISSUE names, plus the machinery around
them:

* decode-with-KV-cache logits must match the full-forward model apply
  ULP-close, on a 1- AND 4-device CPU mesh, for the dense transformer
  and the MoE model (the cache-aware incremental path must not fork the
  math);
* greedy decodes are bitwise reproducible run-to-run;
* exactly TWO compiled programs per serve run (one prefill, one decode
  superstep), warmup included;
* slot admission/eviction edge cases: empty batch, all-full admission,
  mid-scan completion, forced eviction at a full cache page;
* the SLO verdict lane: shared rules-table thresholds (env overrides at
  call time), the scheduler's on-line alerts, the report's serving
  section, and the serve CLI driven end to end on a scripted 4-device
  CPU mesh in a subprocess.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpudist import rules as rules_lib
from tpudist import verdict as verdict_lib
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.models import get_model
from tpudist.obs import report as report_lib
from tpudist.parallel import build_mesh
from tpudist.parallel import sharding as shd
from tpudist.serve import kvcache, slo
from tpudist.serve import scheduler as sched
from tpudist.serve import tune as serve_tune
from tpudist.serve.engine import ServeEngine, init_params

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)
# capacity_factor=4.0 makes routing DROPLESS (cap >= any per-expert
# assignment count), which is what makes MoE serving parity testable at
# all: capacity-bounded routing drops tokens as a function of the WHOLE
# routed batch, so a capacity-bound model's decode logits legitimately
# depend on batch composition — the ULP anchor in the ISSUE names the
# dense transformer; the MoE pin is per-token expert math at decode
# shapes, graded where routing decisions are batch-independent.
TINY_MOE = ModelConfig(name="moe", vocab_size=64, n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       max_seq_len=32, n_experts=4, expert_top_k=2,
                       capacity_factor=4.0)
CFGS = {"transformer": TINY_TF, "moe": TINY_MOE}


def _ref_logits(model, params, seq) -> np.ndarray:
    """Full-forward reference: logits (seq, vocab) f32 for one sequence
    through the TRAINING path (no cache) — the anchor the cached path
    is graded against."""
    cfg = CFGS[_model_name(model)]
    out = model.hidden_states(params, jnp.asarray(seq, jnp.int32)[None],
                              cfg, dtype=jnp.float32)
    h = out[0] if isinstance(out, tuple) else out
    emb = params["embed"].astype(jnp.float32)
    return np.asarray((h @ emb.T).astype(jnp.float32))[0]


def _model_name(model) -> str:
    return model.__name__.rsplit(".", 1)[-1]


def _assert_ulp_close(a: np.ndarray, b: np.ndarray, ulps: int = 64,
                      what: str = "") -> None:
    """|a - b| within ``ulps`` f32 ULPs of the logit SCALE — float
    accumulation error rides the dominant summand magnitude, so a
    near-zero logit legitimately carries the big logits' rounding.
    "The same math up to reassociation": far tighter than any rtol
    that would also pass a genuinely different attention."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = np.float32(max(np.abs(a).max(), np.abs(b).max(), 1.0))
    tol = ulps * np.spacing(np.maximum(
        np.maximum(np.abs(a), np.abs(b)), scale))
    bad = np.abs(a - b) > tol
    assert not bad.any(), (
        f"{what}: {int(bad.sum())}/{bad.size} logits beyond {ulps} "
        f"ULPs; max |d|={float(np.abs(a - b).max()):.3e}")


# ------------------------------------------------------------------ #
# correctness anchor: cached logits vs full forward, 1- and 4-device  #
# ------------------------------------------------------------------ #

# moe variants are the suite's slowest compiles; the tier-1 lane keeps
# the transformer reference anchor plus the paged-vs-dense moe token
# parity (test_paged_serve), the full moe reference check rides the
# slow suite
@pytest.mark.parametrize("model_name", [
    "transformer", pytest.param("moe", marks=pytest.mark.slow)])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_cached_logits_match_full_forward(devices8, model_name, n_dev):
    """Prefill seeds the cache, then each decode step's logits must
    match the full forward over the growing true sequence ULP-close —
    per slot, at per-slot positions (the continuous batch decodes 4
    sequences of DIFFERENT lengths in one program)."""
    cfg = CFGS[model_name]
    model = get_model(model_name)
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    b, pad, max_seq = 4, 8, 16
    lens = [3, 5, 8, 2]
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, cfg.vocab_size, size=(b, pad)).astype(
        np.int32)

    spec = kvcache.CacheSpec.from_model(cfg, slots=b, max_seq=max_seq)
    cache = kvcache.init_cache(spec, mesh)
    h, cache = model.hidden_states(
        params, jnp.asarray(prompts), cfg, dtype=jnp.float32,
        kv_cache=cache, cur_index=None)
    emb = params["embed"].astype(jnp.float32)
    prefill_logits = np.asarray((h @ emb.T).astype(jnp.float32))

    seqs = [list(prompts[i, :lens[i]]) for i in range(b)]
    last = np.zeros((b,), np.int32)
    for i in range(b):
        ref = _ref_logits(model, params, seqs[i])
        _assert_ulp_close(prefill_logits[i, lens[i] - 1], ref[-1],
                          what=f"{model_name}/{n_dev}dev prefill "
                               f"slot{i}")
        last[i] = int(np.argmax(ref[-1]))
        seqs[i].append(int(last[i]))

    pos = np.asarray(lens, np.int32)
    for step in range(4):
        h, cache = model.hidden_states(
            params, jnp.asarray(last[:, None]), cfg, dtype=jnp.float32,
            kv_cache=cache, cur_index=jnp.asarray(pos))
        dec = np.asarray((h[:, 0] @ emb.T).astype(jnp.float32))
        for i in range(b):
            ref = _ref_logits(model, params, seqs[i])
            _assert_ulp_close(dec[i], ref[-1],
                              what=f"{model_name}/{n_dev}dev step{step} "
                                   f"slot{i}")
            assert int(np.argmax(dec[i])) == int(np.argmax(ref[-1]))
            last[i] = np.int32(np.argmax(dec[i]))
            seqs[i].append(int(last[i]))
        pos = pos + 1


@pytest.mark.parametrize("model_name", [
    "transformer", pytest.param("moe", marks=pytest.mark.slow)])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_engine_greedy_matches_reference(devices8, model_name, n_dev):
    """The whole engine+scheduler lane (two compiled programs, masked
    superstep, continuous admission) must greedily decode the SAME
    token sequences as a naive full-forward greedy loop."""
    cfg = CFGS[model_name]
    model = get_model(model_name)
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    engine = ServeEngine(cfg, mesh, slots=2, max_seq=32, prompt_pad=8,
                         decode_k=4)
    engine.warmup(params)
    requests = sched.make_requests(5, prompt_pad=8,
                                   vocab_size=cfg.vocab_size,
                                   max_new=6, rate=0.0, seed=3)
    summary = sched.run_serve(engine, params, requests)
    engine.assert_two_programs()
    assert summary["completed"] == 5 and summary["truncated"] == 0
    for req in requests:
        seq = list(req.tokens[:req.prompt_len])
        want = []
        for _ in range(req.max_new):
            want.append(int(np.argmax(_ref_logits(model, params,
                                                  seq)[-1])))
            seq.append(want[-1])
        got = summary["results"][req.rid]["tokens"]
        assert got == want, (
            f"{model_name}/{n_dev}dev rid{req.rid}: {got} != {want}")


def test_greedy_decode_bitwise_run_to_run(devices8):
    """Two fresh serve runs of the same seed produce byte-identical
    outputs — serving is a pure function of (params, request stream)."""
    outs = []
    for _ in range(2):
        mesh = build_mesh(ParallelConfig(), devices=devices8[:4])
        params = init_params(TINY_TF, mesh, seed=1)
        engine = ServeEngine(TINY_TF, mesh, slots=4, max_seq=32,
                             prompt_pad=8, decode_k=8)
        engine.warmup(params)
        requests = sched.make_requests(8, prompt_pad=8, vocab_size=64,
                                       max_new=10, rate=0.0, seed=11)
        s = sched.run_serve(engine, params, requests)
        outs.append({rid: r["tokens"] for rid, r in s["results"].items()})
    assert outs[0] == outs[1]


# ------------------------------------------------------------------ #
# the two-program pin + slot state machine edges                      #
# ------------------------------------------------------------------ #

def _tiny_engine(devices8, **kw):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 16)
    kw.setdefault("prompt_pad", 4)
    kw.setdefault("decode_k", 4)
    return ServeEngine(TINY_TF, mesh, **kw), params


def test_exactly_two_compiled_programs(devices8):
    """Warmup + a full continuous-batching run with mixed prompt
    lengths, admissions at every occupancy, and mid-run completions:
    one prefill trace, one decode trace, nothing else."""
    engine, params = _tiny_engine(devices8, slots=2)
    engine.warmup(params)
    requests = sched.make_requests(7, prompt_pad=4, vocab_size=64,
                                   max_new=5, rate=0.0, seed=5)
    sched.run_serve(engine, params, requests)
    assert engine.compile_counts() == (1, 1)
    engine.assert_two_programs()


def test_two_program_pin_trips_on_violation(devices8):
    engine, params = _tiny_engine(devices8)
    engine.warmup(params)
    engine.prefill_traces.append(1)     # simulate a retrace
    with pytest.raises(AssertionError, match="two-program"):
        engine.assert_two_programs()


def test_decode_empty_batch_is_noop(devices8):
    """No active slot: the lax.cond skip path passes the state through
    untouched (bitwise) and every token is an invalid placeholder."""
    engine, params = _tiny_engine(devices8)
    state = engine.init_state()
    before = jax.tree.map(np.asarray, state)
    state2, toks, valid = engine.decode(params, state)
    assert not np.asarray(valid).any()
    assert (np.asarray(toks) == -1).all()
    after = jax.tree.map(np.asarray, state2)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)


def test_mid_scan_completion_masks_tail(devices8):
    """A slot whose budget exhausts mid-superstep stops exactly there:
    k=4 dispatch over a remaining=2 slot yields 2 valid tokens and a
    frozen slot for the tail iterations."""
    engine, params = _tiny_engine(devices8, decode_k=4)
    state = engine.init_state()
    prompt = np.arange(4, dtype=np.int32)
    # max_new=3 -> prefill produces token 1, remaining=2
    state, _ = engine.prefill(params, state, prompt[None], 3, 0, 3)
    state, toks, valid = engine.decode(params, state)
    v = np.asarray(valid)[:, 0]
    np.testing.assert_array_equal(v, [True, True, False, False])
    assert not np.asarray(state.active)[0]
    assert int(np.asarray(state.remaining)[0]) == 0
    # the other slot stayed empty through the whole scan
    assert not np.asarray(valid)[:, 1].any()


def test_eviction_at_full_cache_page(devices8):
    """prompt_len + budget past max_seq: the slot is force-evicted when
    its page fills, the result is flagged truncated, and the cache
    write position never leaves the page."""
    engine, params = _tiny_engine(devices8, max_seq=8, prompt_pad=4)
    requests = sched.make_requests(1, prompt_pad=4, vocab_size=64,
                                   max_new=100, rate=0.0, seed=0)
    engine.warmup(params)
    summary = sched.run_serve(engine, params, requests)
    assert summary["truncated"] == 1
    res = summary["results"][0]
    assert res["why"] == "evicted"
    # the final generated token needs no cache row, so a page of
    # max_seq rows carries exactly max_seq + 1 sequence positions —
    # host eviction is aligned with the device freeze, so the
    # truncated length does not depend on decode_k
    assert res["prompt_len"] + res["generated"] == 8 + 1


def test_all_full_admission_queues(devices8):
    """More requests than slots: the overflow queues (visible in
    queue_depth_max) and every request still completes."""
    engine, params = _tiny_engine(devices8, slots=1)
    engine.warmup(params)
    requests = sched.make_requests(4, prompt_pad=4, vocab_size=64,
                                   max_new=4, rate=0.0, seed=2)
    summary = sched.run_serve(engine, params, requests)
    assert summary["completed"] == 4
    assert summary["queue_depth_max"] >= 2
    assert engine.compile_counts() == (1, 1)


def test_engine_arg_validation(devices8):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    with pytest.raises(ValueError, match="--slots"):
        ServeEngine(TINY_TF, mesh, slots=0, max_seq=16, prompt_pad=4)
    with pytest.raises(ValueError, match="decode-steps"):
        ServeEngine(TINY_TF, mesh, slots=1, max_seq=16, prompt_pad=4,
                    decode_k=0)
    with pytest.raises(ValueError, match="prompt_pad"):
        ServeEngine(TINY_TF, mesh, slots=1, max_seq=16, prompt_pad=32)


# ------------------------------------------------------------------ #
# KV cache: spec, layouts, sharding                                   #
# ------------------------------------------------------------------ #

def test_cache_spec_gqa_compact():
    spec = kvcache.CacheSpec.from_model(TINY_TF, slots=4, max_seq=16)
    assert spec.n_kv_heads == 2          # compact, not n_heads=4
    assert spec.canonical_shape == (2, 4, 16, 2, 8)
    assert spec.bytes == 2 * 2 * 4 * 16 * 2 * 8 * 4


def test_cache_layout_roundtrip():
    spec = kvcache.CacheSpec.from_model(TINY_TF, slots=4, max_seq=16,
                                        layout="hs")
    assert spec.storage_shape == (2, 4, 2, 16, 8)
    x = jnp.arange(np.prod(spec.storage_shape),
                   dtype=jnp.float32).reshape(spec.storage_shape)
    rt = kvcache.from_canonical(kvcache.to_canonical(x, "hs"), "hs")
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(x))
    with pytest.raises(ValueError, match="layout"):
        kvcache.to_canonical(x, "zz")


@pytest.mark.parametrize("layout", ["st", "hs"])
def test_cache_sharded_over_mesh(devices8, layout):
    """Slots ride the batch axes: a 4-slot cache on a 4-device data
    mesh puts one slot page per device; odd slot counts sanitise to
    replicated instead of erroring."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:4])
    spec = kvcache.CacheSpec.from_model(TINY_TF, slots=4, max_seq=16,
                                        layout=layout)
    cache = kvcache.init_cache(spec, mesh)
    shard_shapes = {s.data.shape for s in cache["k"].addressable_shards}
    want = list(spec.storage_shape)
    want[1] = 1
    assert shard_shapes == {tuple(want)}
    odd = kvcache.CacheSpec.from_model(TINY_TF, slots=3, max_seq=16,
                                       layout=layout)
    c3 = kvcache.init_cache(odd, mesh)
    assert {s.data.shape for s in c3["k"].addressable_shards} \
        == {odd.storage_shape}


def test_kv_cache_specs_table():
    assert shd.kv_cache_specs("st") == shd.P(
        None, ("data", "fsdp"), None, "tensor", None)
    assert shd.kv_cache_specs("hs") == shd.P(
        None, ("data", "fsdp"), "tensor", None, None)
    with pytest.raises(ValueError, match="layout"):
        shd.kv_cache_specs("sx")


# ------------------------------------------------------------------ #
# SLO math + rules-table wiring                                       #
# ------------------------------------------------------------------ #

def test_percentile_nearest_rank():
    assert slo.percentile([], 99) is None
    assert slo.percentile([5.0], 50) == 5.0
    xs = [float(i) for i in range(1, 101)]
    assert slo.percentile(xs, 50) == 50.0
    assert slo.percentile(xs, 99) == 99.0
    assert slo.percentile(xs, 100) == 100.0


def test_grade_fold_and_delegation(monkeypatch):
    g = slo.grade(None, None, None)
    assert g["status"] == slo.UNGATEABLE
    assert verdict_lib.serve_status(None, None, None) \
        == verdict_lib.UNGATEABLE
    ok = slo.grade(0.5, 0.1, 100.0)
    assert ok["status"] == slo.SUCCESS
    assert {ok["ttft_status"], ok["itl_status"],
            ok["tokens_per_chip_status"]} == {slo.SUCCESS}
    # a missing gate among measured ones does not read UNGATEABLE
    part = slo.grade(0.5, None, 100.0)
    assert part["itl_status"] == slo.UNGATEABLE
    assert part["status"] == slo.SUCCESS
    # env overrides are read at CALL time through the shared table
    monkeypatch.setenv("TPUDIST_TTFT_P99_MAX", "0.1")
    bad = slo.grade(0.5, 0.1, 100.0)
    assert bad["ttft_status"] == slo.FAIL and bad["status"] == slo.FAIL
    assert verdict_lib.serve_status(0.5, 0.1, 100.0) == verdict_lib.FAIL


def test_serve_rules_in_shared_table():
    names = {t.name for t in rules_lib.THRESHOLDS}
    assert {"ttft", "itl", "tokens_per_chip"} <= names
    assert rules_lib.resolve("ttft") == rules_lib.TTFT_P99_MAX
    assert rules_lib.resolve("itl") == rules_lib.ITL_P99_MAX
    assert rules_lib.resolve("tokens_per_chip") \
        == rules_lib.TOKENS_PER_CHIP_MIN
    assert rules_lib.breached("tokens_per_chip",
                              rules_lib.TOKENS_PER_CHIP_MIN / 2)
    assert not rules_lib.breached("ttft", 0.0)
    # all three are live alert rules
    assert {"ttft", "itl", "tokens_per_chip"} <= {
        t.name for t in rules_lib.ALERT_RULES}


def test_run_serve_slo_fail_fires_online_alert(devices8, monkeypatch):
    """An unreachable throughput floor makes the SAME run grade FAIL at
    exit AND fire the tokens_per_chip alert mid-run — consumer parity
    between the scheduler's on-line engine and the exit verdict."""
    monkeypatch.setenv("TPUDIST_TOKENS_PER_CHIP_MIN", "1e12")
    engine, params = _tiny_engine(devices8)
    engine.warmup(params)
    requests = sched.make_requests(3, prompt_pad=4, vocab_size=64,
                                   max_new=4, rate=0.0, seed=1)
    summary = sched.run_serve(engine, params, requests)
    assert summary["status"] == slo.FAIL
    assert summary["tokens_per_chip_status"] == slo.FAIL
    assert summary["alert_events"] >= 1
    assert summary["thresholds"]["tokens_per_chip"] == 1e12


def test_poisson_arrivals_seeded():
    a = sched.make_requests(16, prompt_pad=8, vocab_size=64, max_new=4,
                            rate=100.0, seed=9)
    b = sched.make_requests(16, prompt_pad=8, vocab_size=64, max_new=4,
                            rate=100.0, seed=9)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))
    assert all(1 <= r.prompt_len <= 8 for r in a)
    closed = sched.make_requests(4, prompt_pad=8, vocab_size=64,
                                 max_new=4, rate=0.0, seed=9)
    assert {r.arrival_s for r in closed} == {0.0}


# ------------------------------------------------------------------ #
# serve autotuner: search discipline + fingerprint cache              #
# ------------------------------------------------------------------ #

def _scripted_measure(curve, layouts=None):
    """A fake probe: tokens/s by decode_k from ``curve``, scaled per
    layout by ``layouts`` (default: hs slightly worse)."""
    layouts = layouts or {"st": 1.0, "hs": 0.9}
    calls = []

    def measure(cand):
        calls.append(cand)
        tps = curve.get(cand.decode_k, 0.0) * layouts[cand.layout]
        if tps <= 0:
            return serve_tune.ServeProbeResult(0.0, float("inf"),
                                               feasible=False,
                                               error="scripted OOM")
        return serve_tune.ServeProbeResult(tps, 1.0)

    measure.calls = calls
    return measure


def test_search_picks_plateau_smallest_k():
    curve = {1: 100.0, 2: 190.0, 4: 360.0, 8: 365.0, 16: 366.0,
             32: 350.0}
    m = _scripted_measure(curve)
    out = serve_tune._search(m, serve_tune.ServeCandidate(decode_k=1),
                             max_decode_k=32, trial_budget=16)
    # 4 is within PLATEAU_TOL of the axis best (366): smallest wins
    assert out["best"].decode_k == 4
    assert out["best_tps"] >= out["baseline_tps"]


def test_search_never_commits_slower_than_start():
    curve = {8: 500.0, 1: 100.0, 2: 120.0, 4: 130.0, 16: 90.0,
             32: 80.0}
    m = _scripted_measure(curve)
    out = serve_tune._search(m, serve_tune.ServeCandidate(decode_k=8),
                             max_decode_k=32, trial_budget=16)
    assert out["best"].decode_k == 8
    assert out["best_tps"] == 500.0


def test_search_layout_needs_a_real_win():
    curve = {1: 100.0, 2: 200.0, 4: 200.0}
    # hs measures 1% better: inside PLATEAU_TOL, start's layout keeps
    m = _scripted_measure(curve, layouts={"st": 1.0, "hs": 1.01})
    out = serve_tune._search(m, serve_tune.ServeCandidate(decode_k=1),
                             max_decode_k=4, trial_budget=16)
    assert out["best"].layout == "st"
    m2 = _scripted_measure(curve, layouts={"st": 1.0, "hs": 1.5})
    out2 = serve_tune._search(m2, serve_tune.ServeCandidate(decode_k=1),
                              max_decode_k=4, trial_budget=16)
    assert out2["best"].layout == "hs"


def test_search_infeasible_point_prunes():
    curve = {1: 100.0, 2: 200.0, 4: 0.0, 8: 400.0}   # 4 OOMs
    m = _scripted_measure(curve)
    out = serve_tune._search(m, serve_tune.ServeCandidate(decode_k=1),
                             max_decode_k=8, trial_budget=16)
    assert out["best"].decode_k == 2      # the walk stops at the wall
    assert out["pruned"] >= 1


def test_validate_serve_tuned():
    # the paged axes are part of the schema now — a pre-paging 2-key
    # record is stale by construction and must re-probe
    assert serve_tune.validate_serve_tuned(
        {"decode_k": 8, "layout": "st",
         "kv_page_tokens": 0, "speculate_k": 0})
    assert not serve_tune.validate_serve_tuned({"decode_k": 8,
                                                "layout": "st"})
    assert not serve_tune.validate_serve_tuned(
        {"decode_k": 0, "layout": "st",
         "kv_page_tokens": 0, "speculate_k": 0})
    assert not serve_tune.validate_serve_tuned(
        {"decode_k": 8, "layout": "zz",
         "kv_page_tokens": 0, "speculate_k": 0})


def test_autotune_serve_cache_hit_zero_trials(devices8, tmp_path,
                                              monkeypatch):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    probes = []

    def fake_probe(model_cfg, mesh, params, cand, **kw):
        probes.append(cand)
        return serve_tune.ServeProbeResult(
            100.0 * cand.decode_k if cand.decode_k <= 4 else 390.0, 1.0)

    monkeypatch.setattr(serve_tune, "probe_candidate", fake_probe)
    kw = dict(slots=2, max_seq=32, prompt_pad=8, mode="probe",
              cache_dir=str(tmp_path))
    out = serve_tune.autotune_serve(TINY_TF, mesh, None, **kw)
    assert out.source == "probe" and out.trials == len(probes) > 0
    n = len(probes)
    again = serve_tune.autotune_serve(TINY_TF, mesh, None, **kw)
    assert again.source == "cache" and again.trials == 0
    assert len(probes) == n                  # zero new probes
    assert again.tuned == out.tuned
    # cache-only on a cold fingerprint stays on the heuristics
    cold = serve_tune.autotune_serve(
        TINY_MOE, mesh, None, slots=2, max_seq=32, prompt_pad=8,
        mode="cache-only", cache_dir=str(tmp_path))
    assert cold.source == "heuristic" and cold.trials == 0


def test_autotune_serve_off_and_probe_failure(devices8, tmp_path,
                                              monkeypatch):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    out = serve_tune.autotune_serve(
        TINY_TF, mesh, None, slots=2, max_seq=32, prompt_pad=8,
        mode="off", cache_dir=str(tmp_path))
    assert out.source == "heuristic" and out.trials == 0

    def boom(*a, **k):
        raise RuntimeError("scripted probe crash")

    monkeypatch.setattr(serve_tune, "_search", boom)
    out2 = serve_tune.autotune_serve(
        TINY_TF, mesh, None, slots=2, max_seq=32, prompt_pad=8,
        mode="probe", cache_dir=str(tmp_path / "cold"))
    assert out2.source == "heuristic"        # degrade, never a dead run


# ------------------------------------------------------------------ #
# report: the serving section                                         #
# ------------------------------------------------------------------ #

def _serve_metrics(status="success", tps=50.0):
    return [
        {"kind": "serve_tick", "t_s": 0.1, "queue_depth": 3,
         "active_slots": 2, "completed": 1, "ttft_p99_s": 0.02,
         "itl_p99_s": 0.001, "tokens_per_sec_per_chip": tps},
        {"kind": "serve", "requests": 8, "completed": 8,
         "generated_tokens": 64, "truncated": 0, "wall_s": 1.25,
         "slots": 4, "decode_k": 8, "kv_layout": "st",
         "kv_cache_bytes": 1 << 20, "tokens_per_sec": tps * 4,
         "tokens_per_sec_per_chip": tps, "ttft_p50_s": 0.01,
         "ttft_p99_s": 0.02, "itl_p50_s": 0.001, "itl_p99_s": 0.002,
         "e2e_p99_s": 0.5, "prefill_compiles": 1, "decode_compiles": 1,
         "queue_depth_max": 3, "status": status},
    ]


def test_report_serving_section_and_verdict():
    rep = report_lib.build_report(_serve_metrics(), {})
    sv = rep["serving"]
    assert sv["enabled"] and sv["status"] == "success"
    # serve_shed reads ungateable on a pre-resilience record (no
    # shed_fraction measured) — never a retroactive fail
    assert sv["gates"] == {"ttft": "success", "itl": "success",
                           "tokens_per_chip": "success",
                           "serve_shed": "ungateable"}
    assert sv["queue_over_time"][0]["queue_depth"] == 3
    assert rep["verdict"] == report_lib.SUCCESS
    assert rep["schema"] == report_lib.REPORT_SCHEMA_VERSION  # >=5 adds
    # the Goodput section after the serving one this test pins
    md = report_lib.to_markdown(rep)
    assert "## Serving (latency SLOs)" in md
    assert "serve_status: success" in md
    # a training-only run has no serving section to grade
    rep2 = report_lib.build_report([{"kind": "epoch"}], {})
    assert rep2["serving"] == {"enabled": False}


def test_report_serving_prints_what_the_engine_converted():
    """The ``weights_resident`` span (one a params tree the engine had to
    convert to its dtype) is the serving section's one line; a run whose
    weights were at rest in that dtype already has no span and no line."""
    ev = {"ph": "X", "pid": 0, "tid": 1, "cat": "serve", "ts": 0.0,
          "name": "weights_resident", "dur": 15e3,
          "args": {"leaves": 11, "leaves_cast": 11,
                   "bytes_in": 6_798_319_616, "bytes_out": 3_399_159_808}}
    rep = report_lib.build_report(_serve_metrics(), {"traceEvents": [ev]})
    assert rep["serving"]["weights_resident"] == {
        "trees": 1, "seconds": 0.015, "leaves": 11, "leaves_cast": 11,
        "bytes_in": 6_798_319_616, "bytes_out": 3_399_159_808}
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "weights at rest" in ln)
    assert "11 of 11 leaves" in line
    assert "6.798 GB -> 3.399 GB in 0.015s (1 tree(s))" in line
    plain = report_lib.build_report(_serve_metrics(), {})
    assert plain["serving"]["weights_resident"] is None
    assert "weights at rest" not in report_lib.to_markdown(plain)


def test_report_serving_regrades_through_rules(monkeypatch):
    """The report does not trust the run's own grade: the section
    re-grades the measured numbers through the rules table at fold
    time, so a FAIL-worthy latency fails the report verdict."""
    monkeypatch.setenv("TPUDIST_ITL_P99_MAX", "0.0001")
    rep = report_lib.build_report(_serve_metrics(status="success"), {})
    assert rep["serving"]["gates"]["itl"] == "fail"
    assert rep["serving"]["status"] == "fail"
    assert rep["verdict"] == report_lib.FAIL


def test_report_ungateable_serving_is_not_a_pass():
    """A serve record that measured nothing (all SLO fields None) must
    fold to an UNGATEABLE report verdict, matching the serve CLI's own
    exit grade for the same run — serving-enabled-but-empty is not
    evidence of success."""
    rec = {"kind": "serve", "requests": 0, "completed": 0,
           "generated_tokens": 0, "ttft_p99_s": None, "itl_p99_s": None,
           "tokens_per_sec_per_chip": None}
    rep = report_lib.build_report([rec], {})
    assert rep["serving"]["enabled"]
    assert rep["serving"]["status"] == report_lib.UNGATEABLE
    assert rep["verdict"] == report_lib.UNGATEABLE


def test_report_serving_baseline_ratio(tmp_path):
    base = {"metric": "serve_tokens_per_sec_per_chip", "value": 25.0}
    rep = report_lib.build_report(_serve_metrics(tps=50.0), {},
                                  baseline=base)
    assert rep["serving"]["tokens_per_chip_ratio"] == 2.0
    # prior-report shape works too
    rep2 = report_lib.build_report(
        _serve_metrics(tps=50.0), {},
        baseline={"serving": {"tokens_per_sec_per_chip": 100.0}})
    assert rep2["serving"]["tokens_per_chip_ratio"] == 0.5


# ------------------------------------------------------------------ #
# end to end: the serve CLI on a scripted 4-device CPU mesh           #
# ------------------------------------------------------------------ #

@pytest.mark.slow
def test_serve_cli_e2e_4dev_mesh(tmp_path, monkeypatch):
    """``python -m tpudist.serve`` in a subprocess pinned to a 4-device
    CPU mesh: green SLO verdict, exit 0, BENCH_SERVE.json in the shared
    artifact shape, kind=serve metrics, verdict file, and the report
    CLI folds the serving section from the run's own artifacts."""
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "TPUDIST_VERDICT_PATH": str(tmp_path / "verdict.txt"),
        # decouple the green-verdict pin from machine load: the test
        # grades the WIRING (a breach still fails, see the exit-code
        # test), not this box's latency under a parallel CI build
        "TPUDIST_TTFT_P99_MAX": "120", "TPUDIST_ITL_P99_MAX": "60",
        "TPUDIST_TOKENS_PER_CHIP_MIN": "0.001",
    })
    env.pop("TPUDIST_STAGING_BUDGET_MB", None)
    bench = tmp_path / "BENCH_SERVE.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tpudist.serve", "--requests", "12",
         "--max-new-tokens", "8", "--request-rate", "200",
         "--save-dir", str(tmp_path), "--bench-out", str(bench)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    assert "tpudist: serve success" in proc.stdout

    doc = json.loads(bench.read_text())
    assert doc["metric"] == "serve_tokens_per_sec_per_chip"
    assert doc["value"] > 0
    assert doc["slo"]["status"] == "success"
    assert doc["detail"]["prefill_compiles"] == 1
    assert doc["detail"]["decode_compiles"] == 1
    assert doc["detail"]["n_chips"] == 4

    recs = [json.loads(l) for l in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    serves = [r for r in recs if r.get("kind") == "serve"]
    assert len(serves) == 1 and serves[0]["status"] == "success"
    assert (tmp_path / "verdict.txt").read_text().strip() == "success"

    # the report re-grades through the same env-resolved thresholds
    monkeypatch.setenv("TPUDIST_TTFT_P99_MAX", "120")
    monkeypatch.setenv("TPUDIST_ITL_P99_MAX", "60")
    monkeypatch.setenv("TPUDIST_TOKENS_PER_CHIP_MIN", "0.001")
    rep = report_lib.build_report(recs, {}, baseline=doc)
    assert rep["serving"]["enabled"]
    assert rep["serving"]["status"] == "success"
    assert rep["serving"]["tokens_per_chip_ratio"] == 1.0


def test_serve_cli_exit_code_on_slo_fail(tmp_path):
    """An SLO breach exits 1 with the fail verdict written — in
    process via cli.main to keep the fast lane subprocess-free."""
    from tpudist.serve import cli
    os.environ["TPUDIST_TOKENS_PER_CHIP_MIN"] = "1e12"
    os.environ["TPUDIST_VERDICT_PATH"] = str(tmp_path / "v.txt")
    try:
        rc = cli.main(["--requests", "2", "--max-new-tokens", "2",
                       "--save-dir", str(tmp_path)])
    finally:
        del os.environ["TPUDIST_TOKENS_PER_CHIP_MIN"]
        del os.environ["TPUDIST_VERDICT_PATH"]
    assert rc == 1
    assert (tmp_path / "v.txt").read_text().strip() == "fail"


def test_serve_slo_importable_without_jax():
    """The report CLI folds serving sections on machines with no
    accelerator stack: tpudist.serve and serve.slo import with jax
    blocked (subprocess-pinned like the report's own contract)."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "import tpudist.serve, tpudist.serve.slo as slo\n"
        "assert slo.grade(None, None, None)['status'] == 'ungateable'\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


# ------------------------------------------------------------------ #
# review regressions: empty-run grade, queue semantics, probe honesty #
# ------------------------------------------------------------------ #

def test_empty_request_stream_is_ungateable(devices8):
    """A run that measured NOTHING grades UNGATEABLE, not fail: zero
    requests means no throughput observation, and the three-valued
    contract says an empty run must not read as an SLO verdict either
    way (throughput 0.0 would fail the min-sense floor)."""
    engine, params = _tiny_engine(devices8)
    engine.warmup(params)
    summary = sched.run_serve(engine, params, [])
    assert summary["status"] == slo.UNGATEABLE
    assert summary["tokens_per_chip_status"] == slo.UNGATEABLE
    assert summary["tokens_per_sec_per_chip"] is None
    assert summary["generated_tokens"] == 0


def test_queue_depth_counts_only_arrived(devices8):
    """queue_depth is requests WAITING FOR A SLOT — arrival time
    passed, not yet admitted. The deque holds the entire future
    synthetic schedule; counting it whole would show a full queue on an
    idle pod at any low arrival rate."""
    engine, params = _tiny_engine(devices8, slots=2)
    engine.warmup(params)
    # 6 requests spread over ~3 s of schedule on a 2-slot engine that
    # decodes each in milliseconds: nothing ever actually queues
    requests = sched.make_requests(6, prompt_pad=4, vocab_size=64,
                                   max_new=3, rate=2.0, seed=3)
    clock = iter(np.arange(0.0, 600.0, 0.05))
    summary = sched.run_serve(engine, params, requests,
                              clock=lambda: float(next(clock)))
    assert summary["completed"] == 6
    assert summary["queue_depth_max"] <= 2, summary["queue_depth_max"]


def test_probe_tokens_honest_at_oversized_decode_k(devices8):
    """An uncapped start candidate whose decode_k exceeds the cache
    room must not be credited k×dispatches tokens: slots freeze at a
    full page, and an inflated baseline would let the
    never-slower-than-start floor reject genuinely faster points."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    res = serve_tune.probe_candidate(
        TINY_TF, mesh, params,
        serve_tune.ServeCandidate(decode_k=16, layout="st"),
        slots=2, max_seq=16, prompt_pad=4, n_dispatches=4, repeats=1)
    assert res.feasible, res.error
    # room for 16-4=12 decode tokens per slot, not 16
    assert res.tokens == 2 * 12, res


def test_serve_sweep_all_infeasible_is_a_clean_error(monkeypatch):
    """bench --serve-sweep with no feasible point dies with an honest
    SystemExit naming the situation, not a bare max-of-empty
    ValueError (probe failures are pruned points by contract)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_under_test", os.path.join(
            os.path.dirname(__file__), "..", "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    def all_infeasible(*a, **kw):
        return serve_tune.ServeProbeResult(0.0, float("inf"),
                                           feasible=False, error="OOM")

    monkeypatch.setattr(serve_tune, "probe_candidate", all_infeasible)
    with pytest.raises(SystemExit, match="infeasible"):
        bench.run_serve_sweep("/dev/null")
