"""tpudist.serve: the batched inference engine's acceptance pins.

The two correctness anchors the ISSUE names, plus the machinery around
them:

* decode-over-the-KV-pool logits must match the full-forward model
  apply ULP-close, on a 1- AND 4-device CPU mesh, for the dense
  transformer and the MoE model (the paged incremental path must not
  fork the math);
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
from tpudist.serve.engine import PagedServeEngine, init_params

from serve_reference import greedy_tokens, ref_logits

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
    """Prefill seeds the slots' pages, then each ``paged_hidden_states``
    token step's logits must match the full forward over the growing
    true sequence ULP-close — per slot, at per-slot positions (the
    continuous batch decodes 4 sequences of DIFFERENT lengths in one
    program)."""
    cfg = CFGS[model_name]
    model = get_model(model_name)
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    b, pad, max_seq, pt = 4, 8, 16, 4
    lens = [3, 5, 8, 2]
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, cfg.vocab_size, size=(b, pad)).astype(
        np.int32)

    spec = kvcache.PagedCacheSpec.from_model(cfg, slots=b, max_seq=max_seq,
                                             page_tokens=pt)
    scratch = jnp.zeros((spec.n_layers, b, pad, spec.n_kv_heads,
                         spec.head_dim), jnp.float32)
    h, kv = model.prefill_kv_hidden_states(
        params, jnp.asarray(prompts), cfg, dtype=jnp.float32,
        kv_cache={"k": scratch, "v": scratch})
    emb = params["embed"].astype(jnp.float32)
    prefill_logits = np.asarray((h @ emb.T).astype(jnp.float32))

    # each prompt's K/V into the pages its slot was granted
    alloc = kvcache.PageAllocator(spec)
    pools = {n: np.zeros(spec.pool_shape, np.float32) for n in "kv"}
    for i in range(b):
        assert alloc.admit(i, lens[i])
        for p in range(lens[i]):
            page = alloc.table[i, p // pt]
            for n in "kv":      # (L, kv, hd) of position p
                pools[n][:, :, page, p % pt] = np.asarray(kv[n][:, i, p])
    sh = kvcache.paged_cache_shardings(spec, mesh)
    pool_k = jax.device_put(pools["k"], sh)
    pool_v = jax.device_put(pools["v"], sh)

    seqs = [list(prompts[i, :lens[i]]) for i in range(b)]
    last = np.zeros((b,), np.int32)
    for i in range(b):
        ref = ref_logits(cfg, params, seqs[i])
        _assert_ulp_close(prefill_logits[i, lens[i] - 1], ref[-1],
                          what=f"{model_name}/{n_dev}dev prefill "
                               f"slot{i}")
        last[i] = int(np.argmax(ref[-1]))
        seqs[i].append(int(last[i]))

    pos = np.asarray(lens, np.int32)
    for step in range(4):
        for i in range(b):
            assert alloc.ensure(i, int(pos[i]))
        h, pool_k, pool_v = model.paged_hidden_states(
            params, jnp.asarray(last[:, None]), cfg, dtype=jnp.float32,
            pool_k=pool_k, pool_v=pool_v,
            page_table=jnp.asarray(alloc.table, jnp.int32),
            positions=jnp.asarray(pos[:, None]),
            write_ok=jnp.ones((b, 1), bool), page_tokens=pt)
        dec = np.asarray((h[:, 0] @ emb.T).astype(jnp.float32))
        for i in range(b):
            ref = ref_logits(cfg, params, seqs[i])
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
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    engine = PagedServeEngine(cfg, mesh, slots=2, max_seq=32,
                              prompt_pad=8, decode_k=4)
    engine.warmup(params)
    requests = sched.make_requests(5, prompt_pad=8,
                                   vocab_size=cfg.vocab_size,
                                   max_new=6, rate=0.0, seed=3)
    summary = sched.run_serve(engine, params, requests)
    engine.assert_two_programs()
    assert summary["completed"] == 5 and summary["truncated"] == 0
    want = greedy_tokens(cfg, params, requests)
    for req in requests:
        got = summary["results"][req.rid]["tokens"]
        assert got == want[req.rid], (
            f"{model_name}/{n_dev}dev rid{req.rid}: {got} != "
            f"{want[req.rid]}")


def test_greedy_decode_bitwise_run_to_run(devices8):
    """Two fresh serve runs of the same seed produce byte-identical
    outputs — serving is a pure function of (params, request stream)."""
    outs = []
    for _ in range(2):
        mesh = build_mesh(ParallelConfig(), devices=devices8[:4])
        params = init_params(TINY_TF, mesh, seed=1)
        engine = PagedServeEngine(TINY_TF, mesh, slots=4, max_seq=32,
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
    return PagedServeEngine(TINY_TF, mesh, **kw), params


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
        PagedServeEngine(TINY_TF, mesh, slots=0, max_seq=16, prompt_pad=4)
    with pytest.raises(ValueError, match="decode-steps"):
        PagedServeEngine(TINY_TF, mesh, slots=1, max_seq=16, prompt_pad=4,
                         decode_k=0)
    with pytest.raises(ValueError, match="prompt_pad"):
        PagedServeEngine(TINY_TF, mesh, slots=1, max_seq=16,
                         prompt_pad=32)


# ------------------------------------------------------------------ #
# KV pool: spec, sharding; one forward type per model                 #
# ------------------------------------------------------------------ #

def test_cache_spec_gqa_compact():
    spec = kvcache.PagedCacheSpec.from_model(TINY_TF, slots=4, max_seq=16,
                                             page_tokens=4)
    assert spec.n_kv_heads == 2          # compact, not n_heads=4
    # full capacity: 4 slots x 4 pages, and the trash page
    assert spec.pool_shape == (2, 2, 16 + 1, 4, 8)
    assert spec.bytes == 2 * 2 * 2 * 17 * 4 * 8 * 4 + spec.table_bytes


@pytest.mark.parametrize("pages", [7, 8], ids=["divides", "odd"])
def test_pool_sharded_over_mesh(devices8, pages):
    """Pages ride the batch axes: a pool of 7 pages and the trash page
    on a 4-device data mesh puts two pages on each device; a pool the
    axes do not divide (the common case: the trash page makes full
    capacity odd) sanitises to replicated instead of erroring."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:4])
    spec = kvcache.PagedCacheSpec.from_model(
        TINY_TF, slots=4, max_seq=16, page_tokens=4, pages=pages)
    want = list(spec.pool_shape)
    if (pages + 1) % 4 == 0:
        want[2] = (pages + 1) // 4
        assert kvcache.paged_cache_shardings(spec, mesh).spec \
            == shd.paged_kv_cache_specs()
    cache = kvcache.init_paged_cache(spec, mesh)
    for pool in (cache["k"], cache["v"]):
        assert {s.data.shape for s in pool.addressable_shards} \
            == {tuple(want)}


@pytest.mark.parametrize("model_name", ["transformer", "moe"])
def test_hidden_states_returns_one_type(model_name):
    """``hidden_states`` is the training forward and nothing else: no
    argument turns its return into a cache pair (serving has forwards
    of its own, ``prefill_kv_hidden_states`` and
    ``paged_hidden_states``)."""
    import inspect
    cfg = CFGS[model_name]
    model = get_model(model_name)
    accepted = set(inspect.signature(model.hidden_states).parameters)
    assert not {"kv_cache", "cur_index"} & accepted
    params = model.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 5), jnp.int32)
    kinds = set()
    for kw in ({}, {"remat": True}, {"rope_offset": 3},
               {"rope_positions": jnp.arange(5)}):
        out = model.hidden_states(params, toks, cfg, dtype=jnp.float32,
                                  **kw)
        kinds.add(jax.tree.structure(out))
        assert jax.tree.leaves(out)[0].shape == (1, 5, cfg.d_model)
    assert len(kinds) == 1


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

def _scripted_measure(curve):
    """A fake probe: tokens/s by decode_k from ``curve``."""
    calls = []

    def measure(cand):
        calls.append(cand)
        tps = curve.get(cand.decode_k, 0.0)
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


def test_search_infeasible_point_prunes():
    curve = {1: 100.0, 2: 200.0, 4: 0.0, 8: 400.0}   # 4 OOMs
    m = _scripted_measure(curve)
    out = serve_tune._search(m, serve_tune.ServeCandidate(decode_k=1),
                             max_decode_k=8, trial_budget=16)
    assert out["best"].decode_k == 2      # the walk stops at the wall
    assert out["pruned"] >= 1


def test_validate_serve_tuned():
    # the record's keys ARE the candidate's: a pre-paging 2-key record
    # or one that still carries a storage layout is stale by
    # construction and must re-probe
    ok = {"decode_k": 8, "kv_page_tokens": 8, "speculate_k": 0}
    assert serve_tune.validate_serve_tuned(ok)
    assert not serve_tune.validate_serve_tuned({"decode_k": 8})
    assert not serve_tune.validate_serve_tuned({**ok, "layout": "st"})
    assert not serve_tune.validate_serve_tuned({**ok, "decode_k": 0})
    assert not serve_tune.validate_serve_tuned(
        {**ok, "kv_page_tokens": 0})


def test_cached_tuning_with_a_layout_key_is_a_miss(devices8, tmp_path,
                                                   monkeypatch):
    """A tuning persisted while the tuner still walked storage layouts
    is any other stale entry: ``cache-only`` serves the heuristic start,
    ``probe`` measures again and overwrites it — never an error."""
    from tpudist.tune import cache as cache_mod
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    kw = dict(slots=2, max_seq=32, prompt_pad=8, cache_dir=str(tmp_path))
    fp = serve_tune.fingerprint(TINY_TF, mesh, slots=2, max_seq=32,
                                prompt_pad=8)
    cache_mod.store(str(tmp_path), fp, {
        "tuned": {"decode_k": 16, "layout": "hs", "kv_page_tokens": 0,
                  "speculate_k": 0},
        "tokens_per_sec": 9e9}, prefix="serve")
    out = serve_tune.autotune_serve(TINY_TF, mesh, None,
                                    mode="cache-only", **kw)
    assert out.source == "heuristic" and out.trials == 0
    assert out.tuned == serve_tune.ServeCandidate()
    monkeypatch.setattr(
        serve_tune, "probe_candidate",
        lambda *a, **k: serve_tune.ServeProbeResult(100.0, 1.0))
    out = serve_tune.autotune_serve(TINY_TF, mesh, None, mode="probe",
                                    **kw)
    assert out.source == "probe" and out.trials > 0
    again = serve_tune.autotune_serve(TINY_TF, mesh, None,
                                      mode="cache-only", **kw)
    assert again.source == "cache" and again.tuned == out.tuned


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
         "slots": 4, "decode_k": 8, "kv_page_tokens": 64,
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


@pytest.mark.parametrize("path", ["grouped", "loop", None])
def test_report_serving_prints_the_experts_path_and_blocks(path):
    """The engine's ``experts_path`` instant (which way the dropless
    routine was lowered) and the third count (blocks of rows run) reach
    the "experts held here" line; a run without the instant prints the
    counts alone, a model that counts nothing no line."""
    metrics = _serve_metrics()
    metrics[-1].update(moe_pairs_per_expert_mean=14.8,
                       moe_experts_hit_mean=103.0, moe_blocks_mean=104.5,
                       kv_pages_used_peak=7, kv_pages_total=16)
    ev = {"ph": "X", "pid": 0, "tid": 1, "cat": "serve", "ts": 0.0,
          "name": "experts_path", "dur": 0.0,
          "args": {"path": path, "prefill": path}}
    rep = report_lib.build_report(
        metrics, {"traceEvents": [ev] if path else []})
    assert rep["serving"]["experts_path"] == path
    assert rep["serving"]["moe_blocks_mean"] == 104.5
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "experts held here" in ln)
    assert "103.0 expert(s) hit a layer a step in 104.5 block(s)" in line
    assert (f"experts_path {path}" in line) == bool(path)
    plain = report_lib.build_report(_serve_metrics(), {})
    assert plain["serving"]["experts_path"] is None
    assert "experts held here" not in report_lib.to_markdown(plain)


def test_report_serving_prints_the_identity_experts_share():
    """A model with identity experts: their pairs beside all pairs routed,
    one line; a model without them prints none."""
    metrics = _serve_metrics()
    metrics[-1].update(moe_pairs_per_expert_mean=1.79,
                       moe_experts_hit_mean=10.9, moe_blocks_mean=10.9,
                       moe_pairs_zero_mean=739.3, moe_pairs_all_mean=2221.4,
                       moe_zero_share=0.3328,
                       kv_pages_used_peak=7, kv_pages_total=16)
    rep = report_lib.build_report(metrics, {})
    assert rep["serving"]["moe_zero_share"] == 0.3328
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "identity experts" in ln)
    assert "739.3 of 2221.4 pair(s) routed" in line and "33.3 %" in line
    plain = report_lib.build_report(_serve_metrics(), {})
    assert plain["serving"]["moe_zero_share"] is None
    assert "identity experts" not in report_lib.to_markdown(plain)


@pytest.mark.parametrize("fused", [True, False],
                         ids=["with_counts", "record_before_the_counts"])
def test_report_serving_prints_the_block_generation_counts(fused):
    """A model that generates by blocks: forwards a token emitted, the
    commits that rode in a next block's first step and the forwards
    launched, on one line; a record from before the two counts prints the
    line without them, a model that generates token by token no line."""
    metrics = _serve_metrics()
    metrics[-1].update(block_length=4, forwards_per_token=1.2512,
                       tokens_per_dispatch=301.7)
    if fused:
        metrics[-1].update(commits_fused=35_012, forwards_launched=2_324)
    rep = report_lib.build_report(metrics, {})
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "generation by blocks" in ln)
    assert line.startswith("- generation by blocks of 4: 1.2512 forward(s) "
                           "a token emitted")
    assert line.endswith("301.7 token(s) a dispatch over all slots")
    counts = ("35012 commit(s) fused into a next block's first step, "
              "2324 forward(s) launched")
    assert (counts in line) == fused
    assert rep["serving"]["commits_fused"] == (35_012 if fused else None)
    plain = report_lib.build_report(_serve_metrics(), {})
    assert "generation by blocks" not in report_lib.to_markdown(plain)


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
        serve_tune.ServeCandidate(decode_k=16),
        slots=2, max_seq=16, prompt_pad=4, n_dispatches=4, repeats=1)
    assert res.feasible, res.error
    # room for 16-4=12 decode tokens per slot, not 16
    assert res.tokens == 2 * 12, res


# ------------------------------------------------------------------ #
# one engine: the CLI's defaults, and backpressure against the        #
# reference                                                           #
# ------------------------------------------------------------------ #

def _cli_summary(tmp_path, *flags):
    from tpudist.serve import cli
    return cli.run(cli.parse_args(
        ["--requests", "3", "--max-new-tokens", "3", "--trace", "off",
         "--save-dir", str(tmp_path), *flags]))


@pytest.mark.parametrize("max_seq", [64, 32])
def test_cli_serves_pages_by_default(tmp_path, max_seq):
    """No ``--kv-page-tokens``: the run is served from the pool, at the
    page of every chip run on the ledger, held to ``--max-seq``."""
    summary = _cli_summary(tmp_path, "--max-seq", str(max_seq))
    assert summary["completed"] == 3
    assert summary["kv_page_tokens"] == min(64, max_seq)
    assert summary["kv_pages_total"] > 0
    assert summary["kv_pages_used_peak"] >= 1
    assert "kv_layout" not in summary


@pytest.mark.parametrize("flags", [["--kv-page-tokens", "0"],
                                   ["--kv-page-tokens", "-8"],
                                   ["--kv-layout", "st"]],
                         ids=["page0", "page-8", "kv-layout"])
def test_cli_has_no_value_that_selects_an_engine(flags, capsys):
    from tpudist.serve import cli
    with pytest.raises(SystemExit) as e:
        cli.parse_args(flags)
    assert e.value.code == 2
    assert flags[0] in capsys.readouterr().err


def test_cli_speculates_without_a_page_flag(tmp_path):
    summary = _cli_summary(tmp_path, "--speculate-k", "4")
    assert summary["completed"] == 3
    assert summary["speculate_k"] == 4
    assert summary["verify_compiles"] == 1


def test_backpressured_run_emits_the_reference_tokens(devices8):
    """A pool too small for two prompts at once: the second request
    WAITS for pages (``kv_backpressure``, nothing shed), and every
    request still completes with the naive full-forward greedy tokens."""
    from tpudist.obs import trace as trace_lib
    engine, params = _tiny_engine(devices8, slots=2, max_seq=16,
                                  prompt_pad=8, decode_k=2, page_tokens=4,
                                  pages=3)
    engine.warmup(params)
    requests = sched.make_requests(4, prompt_pad=8, vocab_size=64,
                                   max_new=4, rate=0.0, seed=4,
                                   prompt_min=6)
    tracer = trace_lib.configure(enabled=True)
    try:
        summary = sched.run_serve(engine, params, requests)
    finally:
        trace_lib.configure()
    names = [ev["name"] for ev in tracer.events()]
    assert "kv_backpressure" in names
    assert summary["completed"] == 4 and summary["truncated"] == 0
    assert summary["shed_total"] == 0 and summary["active_slots_peak"] == 1
    assert {rid: r["tokens"] for rid, r in summary["results"].items()} \
        == greedy_tokens(TINY_TF, params, requests)
    assert engine.alloc.pages_used() == 0
