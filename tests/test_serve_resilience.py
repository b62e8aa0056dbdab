"""Serve resilience plane (tpudist.serve.resilience + drill): admission
control, deadline shedding, graceful degradation, chaos-drilled engine
supervision.

The ledger/controller/validation tests are in-process and scripted
(virtual clocks, fake metrics sinks) — determinism is the contract
under test. The end-to-end test runs ONE scenario of the drill matrix
(serve_kill — the supervision satellite) through real subprocesses;
the full six-scenario matrix is slow-marked here and runs green in the
CI serve-chaos lane via ``selfcheck check_serve_resilience``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpudist import rules as rules_lib
from tpudist.chaos import inject as inject_mod
from tpudist.chaos import plan as plan_mod
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.obs import report as report_lib
from tpudist.parallel import build_mesh
from tpudist.serve import drill as drill_mod
from tpudist.serve import resilience as res_lib
from tpudist.serve import scheduler as sched
from tpudist.serve import slo
from tpudist.serve.engine import PagedServeEngine, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)


def _tiny_engine(devices8, **kw):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 16)
    kw.setdefault("prompt_pad", 4)
    kw.setdefault("decode_k", 4)
    return PagedServeEngine(TINY_TF, mesh, **kw), params


class RecMetrics:
    """A MetricsLogger stand-in that records instead of writing."""

    def __init__(self):
        self.recs = []

    def log(self, **kv):
        self.recs.append(kv)

    def flush(self):
        pass


# ------------------------------------------------------------ the ledger


def test_shed_ledger_partitions_exactly():
    led = res_lib.ShedLedger()
    assert led.exact and led.shed_fraction() is None
    led.arrived = 10
    led.admitted, led.shed_admission = 6, 2
    led.expired_queue, led.rejected = 1, 1
    led.completed, led.evicted, led.lost = 4, 1, 1
    assert led.admission_exact() and led.outcome_exact() and led.exact
    assert led.shed_total() == 4
    assert led.shed_fraction() == 0.4
    d = led.as_dict()
    assert d["admission_exact"] and d["outcome_exact"]
    # a dropped-on-the-floor request flips the invariant, loudly
    led.arrived = 11
    assert not led.admission_exact() and not led.exact
    led.arrived, led.lost = 10, 2
    assert not led.outcome_exact()


def test_default_ladder_shapes():
    assert res_lib.default_ladder(8) == (8, 4, 2)
    assert res_lib.default_ladder(4) == (4, 2, 1)
    assert res_lib.default_ladder(2) == (2, 1)
    assert res_lib.default_ladder(1) == (1,)
    assert res_lib.default_ladder(8, levels=1) == (8,)


# ----------------------------------------------- pressure + hysteresis


def test_pressure_controller_hysteresis_no_oscillation():
    """A scripted load step: sustained pressure downshifts (once per
    trip_ticks consecutive hot observations), pressure parked BETWEEN
    the trip and clear thresholds holds the level forever (the
    hysteresis band), and only a sustained clear restores — exactly 4
    transitions over the whole script, no oscillation."""
    cfg = res_lib.ResilienceConfig(
        adapt=True, depth_high=5.0, depth_low=1.0,
        trip_ticks=2, clear_ticks=3, window=2)
    pc = res_lib.PressureController(cfg, max_level=2)
    moves = []
    for depth in [10] * 6:                 # load step: sustained hot
        t = pc.observe(depth)
        if t:
            moves.append(t[:2])
    assert moves == [(0, 1), (1, 2)]       # down to the floor, then hold
    assert pc.level == 2
    for depth in [3] * 10:                 # in the hysteresis band
        assert pc.observe(depth) is None   # NO oscillation
    assert pc.level == 2
    for depth in [0] * 8:                  # sustained clear
        t = pc.observe(depth)
        if t:
            moves.append(t[:2])
    assert moves == [(0, 1), (1, 2), (2, 1), (1, 0)]
    assert pc.level == 0
    for depth in [0] * 5:                  # fully clear: stays put
        assert pc.observe(depth) is None
    assert len(pc.transitions) == 4


def test_pressure_controller_itl_axis(monkeypatch):
    cfg = res_lib.ResilienceConfig(
        adapt=True, depth_high=100.0, depth_low=50.0,
        itl_high_s=0.01, itl_low_s=0.001, trip_ticks=1, clear_ticks=1,
        window=1)
    pc = res_lib.PressureController(cfg, max_level=1)
    assert pc.observe(0, itl_s=0.5) == (
        0, 1, "pressure: rolling depth 0.00 / itl 0.5")
    assert pc.observe(0, itl_s=0.0005) is not None   # cleared
    assert pc.level == 0


def test_virtual_clock_monotone():
    clk = res_lib.VirtualClock()
    assert clk() == 0.0
    clk.advance(0.5)
    clk.advance(-1.0)              # negative advances are clamped
    assert clk() == 0.5
    clk.wait_until(0.2)            # never goes backwards
    assert clk() == 0.5
    clk.wait_until(1.0)
    assert clk() == 1.0


# ------------------------------------------- request validation + fuzz


def test_validate_request_accepts_real_stream():
    for r in sched.make_requests(16, prompt_pad=8, vocab_size=64,
                                 max_new=4, rate=100.0, seed=7):
        assert sched.validate_request(r, prompt_pad=8,
                                      vocab_size=64) is None


def test_garbage_request_fuzz_every_mode_rejected():
    """FrameDecoder-style fuzz for the request_garbage family: a large
    seeded batch of malformed requests must cover every corruption
    mode, and EVERY one must be rejected at validation with a named
    reason — garbage costs itself a rejection, never the engine."""
    p = plan_mod.ChaosPlan.parse("request_garbage@0:0,n=48")
    garbage = sched.make_garbage_requests(
        p, p.events[0], rid_base=100, prompt_pad=8, vocab_size=64,
        span_s=1.0)
    assert len(garbage) == 48
    reasons = set()
    for g in garbage:
        why = sched.validate_request(g, prompt_pad=8, vocab_size=64)
        assert why is not None, f"garbage rid {g.rid} slipped through"
        reasons.add(why)
        assert 0.0 <= g.arrival_s <= 1.0
    # seeded variety: the modes map onto these rejection reasons
    assert reasons == {"bad_token", "bad_prompt_len", "bad_max_new",
                       "bad_shape", "bad_dtype"}
    # deterministic: the same plan regenerates the same garbage
    again = sched.make_garbage_requests(
        p, p.events[0], rid_base=100, prompt_pad=8, vocab_size=64,
        span_s=1.0)
    assert [(g.rid, g.arrival_s, g.prompt_len, g.max_new)
            for g in garbage] == \
        [(g.rid, g.arrival_s, g.prompt_len, g.max_new) for g in again]


# --------------------------------------------- chaos plan/runtime serve


def test_plan_parses_serve_families():
    p = plan_mod.ChaosPlan.parse(
        "serve_kill@0:6,rc=137; serve_slow@0:2,s=0.02,steps=4;"
        "request_garbage@0:0,n=6; kill@0:5")
    assert [e.kind for e in p.serve_events] == \
        ["serve_kill", "serve_slow", "request_garbage"]
    assert [e.kind for e in p.step_events] == ["kill"]
    assert set(plan_mod.SERVE_KINDS) == {
        "serve_kill", "serve_slow", "request_garbage"}
    # train FAULT_KINDS unchanged: the train drill matrix still maps
    # onto exactly those seven families
    assert set(plan_mod.FAULT_KINDS) == set(drill_import_families())


def drill_import_families():
    from tpudist.chaos import drill as chaos_drill
    return chaos_drill.FAMILIES


class _Exit(Exception):
    def __init__(self, rc):
        self.rc = rc


def _runtime(spec, **kw):
    rt = inject_mod.ChaosRuntime(plan_mod.ChaosPlan.parse(spec), **kw)

    def fake_exit(rc):
        raise _Exit(rc)
    rt._exit = fake_exit
    return rt


def test_runtime_serve_kill_at_dispatch_boundary(capsys):
    rt = _runtime("serve_kill@0:6,rc=137")
    for d in range(6):
        assert rt.on_serve_dispatch(d) == 0.0
    with pytest.raises(_Exit) as e:
        rt.on_serve_dispatch(6)
    assert e.value.rc == 137 and rt.fired == 1
    assert "chaos fired: serve_kill@0:6" in capsys.readouterr().out


def test_runtime_serve_slow_returns_injected_stall():
    sleeps = []
    rt = _runtime("serve_slow@0:2,s=0.25,steps=3")
    rt._sleep = sleeps.append
    out = [rt.on_serve_dispatch(d) for d in range(8)]
    assert out == [0.0, 0.0, 0.25, 0.25, 0.25, 0.0, 0.0, 0.0]
    assert sleeps == [0.25, 0.25, 0.25]
    assert rt.fired == 1             # one record for the whole burst


def test_runtime_consume_request_garbage_once():
    rt = _runtime("request_garbage@0:0,n=5")
    evs = rt.consume_request_garbage()
    assert [e.kind for e in evs] == ["request_garbage"]
    assert rt.fired == 1
    assert rt.consume_request_garbage() == []      # consumed exactly once


# ------------------------------------- in-process overload + determinism


OVERLOAD_KW = dict(n=40, prompt_pad=4, vocab_size=64, max_new=6,
                   rate=800.0, seed=11)
OVERLOAD_RES = dict(queue_cap=6, ttft_deadline_s=0.025, validate=True)


def _overload_run(devices8, metrics=None, res_kw=None, engine_kw=None):
    engine, params = _tiny_engine(devices8, **(engine_kw or {}))
    engine.warmup(params)
    requests = sched.make_requests(**OVERLOAD_KW)
    virtual = res_lib.VirtualTiming(prefill_s=0.002, decode_s=0.004)
    res = res_lib.ResilienceConfig(**(res_kw or OVERLOAD_RES))
    return sched.run_serve(engine, params, requests, metrics=metrics,
                           resilience=res, virtual=virtual)


def test_overload_exact_partition_and_bounded_ttft(devices8):
    """THE admission-control acceptance pin, in process: ~5x overload
    on a 2-slot engine with a bounded queue and a 25 ms deadline —
    every arrival lands in exactly one bucket, both shed mechanisms
    fire, and the ADMITTED traffic's p99 TTFT stays within one
    scheduler boundary of the deadline instead of inheriting the
    backlog."""
    m = RecMetrics()
    s = _overload_run(devices8, metrics=m)
    part = s["partition"]
    assert part["admission_exact"] and part["outcome_exact"]
    assert s["arrived"] == 40
    assert s["shed_at_admission"] > 0
    assert s["expired_in_queue"] > 0
    assert s["completed"] == s["admitted"]
    # deadline + one dispatch (4 ms) + a slot-refill round of prefills
    assert s["ttft_p99_s"] <= 0.025 + 0.012, s["ttft_p99_s"]
    assert s["ttft_status"] == "success"
    # the event stream tells the same story as the ledger
    events = [r for r in m.recs if r.get("kind") == "serve_request"]
    outcomes = [r["event"] for r in events
                if r["event"] in res_lib.TERMINAL_EVENTS
                or r["event"] == res_lib.ADMITTED]
    assert outcomes.count("admitted") == s["admitted"]
    assert outcomes.count("shed_admission") == s["shed_at_admission"]
    assert outcomes.count("expired_queue") == s["expired_in_queue"]


def test_overload_bitwise_deterministic_run_to_run(devices8):
    """Two fresh virtual-clock runs of the same seed produce the SAME
    summary, bit for bit — shed decisions, percentiles, partition and
    all (the monotonic-clock satellite: no wall-clock reads in the
    decision path)."""
    a = _overload_run(devices8)
    b = _overload_run(devices8)
    assert a == b


def test_deadline_expiry_pops_oldest_first(devices8):
    """In-queue expiry ordering: with every request present at t=0 on
    a 1-slot engine, the queue ages as one cohort and expiry must pop
    the FIFO head (the oldest ask) — expired rids come out in exactly
    arrival (rid) order, and the slotted request is never expired."""
    engine, params = _tiny_engine(devices8, slots=1)
    engine.warmup(params)
    requests = sched.make_requests(6, prompt_pad=4, vocab_size=64,
                                   max_new=6, rate=0.0, seed=2)
    m = RecMetrics()
    virtual = res_lib.VirtualTiming(prefill_s=0.002, decode_s=0.004)
    res = res_lib.ResilienceConfig(ttft_deadline_s=0.004)
    s = sched.run_serve(engine, params, requests, metrics=m,
                        resilience=res, virtual=virtual)
    expired = [r["rid"] for r in m.recs
               if r.get("kind") == "serve_request"
               and r["event"] == res_lib.EXPIRED]
    assert expired == sorted(expired) and len(expired) >= 3
    assert 0 not in expired                  # rid 0 took the slot at t=0
    assert s["partition"]["admission_exact"]


def test_instant_completions_never_drop_the_queue(devices8):
    """Review regression: every admission finishing INSIDE the admit
    pass (max_new=1 completes at prefill) empties the slots while the
    accepted queue is still full — the loop must circle back into
    admit, not read idle slots + drained schedule as done and drop the
    queue on the floor."""
    engine, params = _tiny_engine(devices8, slots=2)
    engine.warmup(params)
    requests = sched.make_requests(6, prompt_pad=4, vocab_size=64,
                                   max_new=1, rate=0.0, seed=4)
    s = sched.run_serve(engine, params, requests)
    assert s["completed"] == 6
    assert s["partition"]["admission_exact"]
    # same trigger through the adapt-time budget cap
    engine2, params2 = _tiny_engine(devices8, slots=2,
                                    adapt_ladder=(4, 1))
    engine2.warmup(params2)
    res = res_lib.ResilienceConfig(adapt=True, max_new_cap=1,
                                   depth_high=0.5, depth_low=0.0,
                                   trip_ticks=1, clear_ticks=99,
                                   window=1)
    reqs = sched.make_requests(8, prompt_pad=4, vocab_size=64,
                               max_new=4, rate=0.0, seed=4)
    s2 = sched.run_serve(engine2, params2, reqs, resilience=res,
                         virtual=res_lib.VirtualTiming())
    assert s2["completed"] == 8
    assert s2["partition"]["admission_exact"]
    # and with a FUTURE arrival still pending: the idle branch must
    # re-admit the waiting queue BEFORE warping the clock to the next
    # arrival — warping first would expire rid 2 (aged 5 s against a
    # 50 ms deadline) with both slots sitting free
    import dataclasses as dc
    engine3, params3 = _tiny_engine(devices8, slots=2)
    engine3.warmup(params3)
    base = sched.make_requests(4, prompt_pad=4, vocab_size=64,
                               max_new=1, rate=0.0, seed=4)
    reqs3 = [dc.replace(r, arrival_s=a)
             for r, a in zip(base, [0.0, 0.0, 0.0, 5.0])]
    res3 = res_lib.ResilienceConfig(ttft_deadline_s=0.05)
    s3 = sched.run_serve(engine3, params3, reqs3, resilience=res3,
                         virtual=res_lib.VirtualTiming())
    assert s3["completed"] == 4 and s3["expired_in_queue"] == 0, \
        s3["partition"]
    assert s3["ttft_p99_s"] < 0.05      # rid 2 served at queue scale


def test_stale_arrival_expires_instead_of_shedding(devices8):
    """Review regression: at one sampled boundary, dead queue heads
    are expired BEFORE fresh arrivals are judged against the cap, and
    an arrival whose own deadline passed in the schedule backlog
    counts expired (never servable), not shed."""
    import dataclasses as dc
    engine, params = _tiny_engine(devices8, slots=1)
    engine.warmup(params)
    # scripted arrivals on a 1-slot engine busy for ~12 ms: rid 0
    # takes the slot, rids 1+2 fill the cap-2 queue and age past the
    # 5 ms deadline, then rid 3 arrives at the same boundary that
    # finds them dead — expire-first means rid 3 is ACCEPTED (and
    # served), not shed against a queue of corpses
    base = sched.make_requests(4, prompt_pad=4, vocab_size=64,
                               max_new=12, rate=0.0, seed=6)
    arrivals = [0.0, 0.001, 0.002, 0.010]
    requests = [dc.replace(r, arrival_s=a)
                for r, a in zip(base, arrivals)]
    m = RecMetrics()
    res = res_lib.ResilienceConfig(queue_cap=2, ttft_deadline_s=0.005)
    s = sched.run_serve(engine, params, requests, metrics=m,
                        resilience=res, virtual=res_lib.VirtualTiming())
    assert s["partition"]["admission_exact"]
    assert s["shed_at_admission"] == 0, s["partition"]
    expired = {r["rid"] for r in m.recs
               if r.get("kind") == "serve_request"
               and r["event"] == res_lib.EXPIRED}
    assert expired == {1, 2}, expired
    assert s["admitted"] == 2 and s["completed"] == 2   # rids 0 and 3


def test_resilience_off_is_bitwise_pre_resilience(devices8):
    """The default config is OFF and must reproduce the open-loop
    scheduler exactly: nothing shed, nothing expired, nothing
    validated away, every request completed — the serve lane's
    existing behavior is unchanged until an operator opts in."""
    engine, params = _tiny_engine(devices8)
    engine.warmup(params)
    requests = sched.make_requests(8, prompt_pad=4, vocab_size=64,
                                   max_new=4, rate=0.0, seed=5)
    s = sched.run_serve(engine, params, requests)
    assert s["completed"] == 8
    assert s["shed_total"] == 0 and s["shed_fraction"] == 0.0
    assert s["partition"]["admission_exact"]
    assert s["serve_shed_status"] == "success"
    assert s["adapt_level"] == 0 and s["adapt_transitions"] == []


# --------------------------------------------- graceful degradation


def test_adapt_downshifts_on_ladder_without_recompile(devices8):
    """Sustained queue pressure downshifts decode_k on the pre-compiled
    ladder (kind=serve_adapt records, no recompile past warmup), and
    the degraded run still greedily decodes the SAME tokens as full
    service — the ladder changes pacing, never the math."""
    m = RecMetrics()
    res_kw = dict(adapt=True, depth_high=4.0, depth_low=1.0,
                  trip_ticks=1, clear_ticks=4, window=2, validate=True)
    s = _overload_run(devices8, metrics=m, res_kw=res_kw,
                      engine_kw=dict(adapt_ladder=(4, 2, 1)))
    trans = [r for r in m.recs if r.get("kind") == "serve_adapt"]
    assert any(t["to_level"] > t["from_level"] for t in trans)
    assert s["decode_k_ladder"] == [4, 2, 1]
    assert (s["prefill_compiles"], s["decode_compiles"]) == (1, 3)
    assert s["completed"] == 40              # no cap: degraded, not shed
    assert s["partition"]["outcome_exact"]
    # token parity vs full service (greedy is k-independent)
    base = _overload_run(devices8, res_kw=dict(validate=True))
    assert {rid: r["tokens"] for rid, r in s["results"].items()} == \
        {rid: r["tokens"] for rid, r in base["results"].items()}


def test_engine_ladder_program_budget(devices8):
    engine, params = _tiny_engine(devices8, adapt_ladder=(4, 2, 1))
    engine.warmup(params)
    assert engine.compile_counts() == (1, 3)
    engine.assert_two_programs()             # 1 prefill + 1 per rung
    # dispatching a warmed rung never retraces
    state = engine.init_state()
    for k in (4, 2, 1):
        state, _, _ = engine.decode(params, state, k)
    assert engine.compile_counts() == (1, 3)
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    with pytest.raises(ValueError, match="ladder"):
        PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16, prompt_pad=4,
                         decode_k=4,
                         adapt_ladder=(4, 4, 2))  # not strictly descending
    with pytest.raises(ValueError, match="ladder"):
        PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16, prompt_pad=4,
                         decode_k=4,
                         adapt_ladder=(8, 4))     # must start at decode_k


# ------------------------------------------------- rules/report wiring


def test_serve_shed_rule_in_shared_table():
    assert rules_lib.resolve("serve_shed") == rules_lib.SERVE_SHED_MAX
    assert rules_lib.get("serve_shed").alert
    assert rules_lib.breached("serve_shed", 0.95)
    assert not rules_lib.breached("serve_shed", 0.0)
    assert ("serve_shed_status", "serve_shed") in \
        rules_lib.SERVE_STATUS_RULES
    assert ("serve_shed", "shed_fraction") in slo.SERVE_RULES
    # env override at call time, like every gate
    os.environ["TPUDIST_SERVE_SHED_MAX"] = "0.05"
    try:
        assert rules_lib.resolve("serve_shed") == 0.05
        assert slo.grade(0.1, 0.1, 10.0, shed_fraction=0.1)[
            "serve_shed_status"] == slo.FAIL
    finally:
        del os.environ["TPUDIST_SERVE_SHED_MAX"]
    assert slo.grade(0.1, 0.1, 10.0, shed_fraction=None)[
        "serve_shed_status"] == slo.UNGATEABLE


def test_report_cross_checks_serve_fail_against_alerts():
    """The report's Alerts section must flag a serve gate that graded
    fail at exit with no matching mid-run alert — the serve twin of
    the STATUS_RULES cross-check, over the shared
    rules.SERVE_STATUS_RULES table."""
    serve_rec = {"kind": "serve", "serve_shed_status": "fail",
                 "ttft_status": "success"}
    sec = report_lib.alerts_section([serve_rec], [], None)
    assert any("serve_shed" in w for w in sec["warnings"]), sec
    fired = [{"kind": "alert", "alert": "serve_shed", "state": "firing",
              "first_ts": 1.0}]
    sec2 = report_lib.alerts_section([serve_rec], fired, None)
    assert not any("serve_shed" in w for w in sec2["warnings"]), sec2


def test_report_serving_section_carries_shed_partition():
    recs = [{"kind": "serve", "requests": 10, "completed": 6,
             "generated_tokens": 30, "wall_s": 1.0, "slots": 2,
             "decode_k": 4, "kv_page_tokens": 8, "ttft_p99_s": 0.01,
             "itl_p99_s": 0.001, "tokens_per_sec_per_chip": 30.0,
             "arrived": 10, "admitted": 6, "shed_at_admission": 2,
             "expired_in_queue": 1, "rejected": 1, "lost": 0,
             "shed_fraction": 0.4, "queue_cap": 4,
             "ttft_deadline_s": 0.025, "adapt_level": 1,
             "queue_depth_max": 4},
            {"kind": "serve_adapt", "t_s": 0.5, "from_level": 0,
             "to_level": 1, "decode_k": 2, "reason": "pressure"}]
    rep = report_lib.build_report(recs, {})
    sv = rep["serving"]
    assert sv["shed_at_admission"] == 2 and sv["expired_in_queue"] == 1
    assert sv["gates"]["serve_shed"] == "success"   # 0.4 <= 0.6 default
    assert sv["adapt_transitions"] == [
        {"t_s": 0.5, "from_level": 0, "to_level": 1, "decode_k": 2,
         "reason": "pressure"}]
    md = report_lib.to_markdown(rep)
    assert "admission: 10 arrived = 6 admitted + 2 shed" in md
    assert "degradation: L0" in md


def test_drill_modules_importable_without_jax():
    """The drill driver, verifier and resilience plane run on the
    launcher/CI host — the same jax-free contract as policy, goodput
    and chaos.verify."""
    code = ("import sys; sys.modules['jax'] = None; "
            "from tpudist.serve import resilience, drill; "
            "from tpudist import rules; "
            "assert set(drill.SCENARIOS) >= {'overload', 'serve_kill'}; "
            "assert rules.SERVE_STATUS_RULES; "
            "led = resilience.ShedLedger(); assert led.exact; "
            "print('ok')")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


# ----------------------------------------------------- end-to-end drill


def test_serve_kill_supervisor_restart_e2e(tmp_path):
    """THE supervision acceptance drill (satellite): a serve_kill at a
    dispatch boundary on the 4-dev CPU mesh — rc 137, the jax-free
    policy classifies preemption and requeues, the resumed attempt
    replays the still-live queued requests and classifies the dead
    attempt's in-flight slots as lost, and every rid ends in exactly
    one terminal bucket across the two attempts."""
    result = drill_mod.run_scenario(str(tmp_path), "serve_kill")
    assert result["rcs"] == [137, 0]
    rep = drill_mod.verify_scenario(str(tmp_path), result)
    assert rep["ok"], rep["problems"]
    facts = rep["facts"]
    assert facts["policy"] == "preemption"
    assert facts["resume"]["lost"] >= 1
    assert facts["terminal_rids"] == 24
    assert facts["attempts"] == [[0, 137, "preemption"],
                                 [1, 0, "success"]] or \
        facts["attempts"] == [(0, 137, "preemption"), (1, 0, "success")]


@pytest.mark.slow
def test_full_resilience_matrix(tmp_path):
    """The whole six-scenario matrix (overload determinism included) —
    slow-marked; the CI serve-chaos lane runs it via selfcheck."""
    report = drill_mod.run_and_verify(str(tmp_path))
    bad = {k: v["problems"]
           for k, v in report["scenarios"].items() if not v["ok"]}
    assert report["ok"] and not bad, bad
    art = drill_mod.bench_artifact(report)
    assert art["value"] == len(drill_mod.SCENARIOS)
