"""Request-flight tracing (tpudist.serve.flight + the serve-lane
tracer instrumentation).

The acceptance pins:

* the flight ledger reconstructs EXACTLY one chain per arrived rid on
  a seeded overloaded run (sheds + expiries firing), with
  ``ttft == queue_wait + prefill`` inside the pinned flight_decomp
  tolerance and chain counts reconciled bitwise against the
  ShedLedger partition;
* the trace presentation transforms: per-slot track copies (tagged,
  re-tid'd, thread-named) and ph="C" KV occupancy counters;
* trace-on vs ``--trace off`` greedy token streams are BITWISE
  identical, and the disabled tracer path reads the clock ZERO times;
* the report folds a schema-7 "Request flights" section — with jax
  blocked, like every report path;
* the live exporter renders native TTFT/ITL histogram families and
  the tail dashboard renders serve rows;
* the ``python -m tpudist.serve.flight`` verifier exits 0 on a clean
  run directory and nonzero on a broken chain.
"""

import json
import os
import subprocess
import sys

import pytest

from tpudist import rules as rules_lib
from tpudist.obs import live as live_lib
from tpudist.obs import report as report_lib
from tpudist.obs import trace as trace_mod
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.parallel import build_mesh
from tpudist.serve import flight as flight_lib
from tpudist.serve import resilience as res_lib
from tpudist.serve import scheduler as sched
from tpudist.serve import slo as slo_lib
from tpudist.serve.engine import PagedServeEngine, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)


class RecMetrics:
    def __init__(self):
        self.recs = []

    def log(self, **kv):
        self.recs.append(kv)

    def flush(self):
        pass


@pytest.fixture
def fresh_tracer():
    """An enabled ambient tracer for the duration of one test (the
    scheduler reads trace.get()); restores the env-resolved default."""
    tr = trace_mod.configure(enabled=True)
    yield tr
    trace_mod.configure()


def _trace_doc(tracer):
    """The minimal trace-document shape the ledger consumes (what a
    worker export writes, without touching disk)."""
    return {"metadata": {"dropped": tracer.dropped},
            "traceEvents": tracer.events(process_index=0)}


# ---------------------------------------------------------- unit: hist


def test_hist_block_shape_and_overflow():
    h = slo_lib.hist_block([0.001, 0.003, 0.003, 99.0],
                           (0.002, 0.004, 0.008))
    assert h["buckets"] == [0.002, 0.004, 0.008]
    # per-bucket counts + one overflow bin, NOT cumulative
    assert h["counts"] == [1, 2, 0, 1]
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(99.007, abs=1e-6)
    empty = slo_lib.hist_block([], (0.5,))
    assert empty["counts"] == [0, 0] and empty["count"] == 0


def test_latency_stats_ship_fixed_bucket_hists():
    st = slo_lib.LatencyStats()
    st.note_ttft(0.02)
    st.note_itl(0.004, 2)
    th, ih = st.ttft_hist(), st.itl_hist()
    assert th["buckets"] == list(slo_lib.TTFT_BUCKETS_S)
    assert th["count"] == 1 and sum(th["counts"]) == 1
    assert ih["buckets"] == list(slo_lib.ITL_BUCKETS_S)
    assert ih["count"] == 2            # n-token dispatch = n samples


# ------------------------------------------- trace presentation helpers


SCRIPTED_EVENTS = [
    {"ph": "X", "cat": "serve", "name": "admitted", "ts": 10.0,
     "dur": 0.0, "pid": 0, "tid": 3, "args": {"rid": 0, "slot": 1}},
    {"ph": "X", "cat": "serve", "name": "arrive", "ts": 5.0,
     "dur": 0.0, "pid": 0, "tid": 3, "args": {"rid": 0}},   # no slot
    {"ph": "X", "cat": "train", "name": "step", "ts": 0.0,
     "dur": 1.0, "pid": 0, "tid": 3, "args": {"slot": 1}},  # wrong cat
    {"ph": "X", "cat": "serve_counter", "name": "kv_pages", "ts": 11.0,
     "dur": 0.0, "pid": 0, "tid": 3,
     "args": {"used": 5, "total": 8, "shared_refs": 3}},
]


def test_slot_track_events_transform():
    out = flight_lib.slot_track_events(SCRIPTED_EVENTS)
    metas = [e for e in out if e["ph"] == "M"]
    copies = [e for e in out if e["ph"] != "M"]
    assert len(copies) == 1                      # only the slotted one
    c = copies[0]
    assert c["tid"] == flight_lib.SLOT_TID_BASE + 1
    assert c["args"]["track"] == "slot"
    assert c["name"] == "admitted"
    # the original is untouched (copies, not mutation)
    assert "track" not in SCRIPTED_EVENTS[0]["args"]
    assert [m["args"]["name"] for m in metas] == ["slot1"]
    # track-tagged copies are NOT re-copied on a second pass
    assert flight_lib.slot_track_events(out) == []


def test_kv_counter_events_transform():
    out = flight_lib.kv_counter_events(SCRIPTED_EVENTS)
    assert [e["ph"] for e in out] == ["C", "C"]
    pages = next(e for e in out if e["name"] == "kv_pages")
    refs = next(e for e in out if e["name"] == "kv_shared_refs")
    assert pages["args"] == {"used": 5, "free": 3}
    assert refs["args"] == {"refs": 3}
    assert pages["ts"] == 11.0


def test_export_pod_trace_counts_counter_events(tmp_path):
    tracer = trace_mod.Tracer(capacity=64)
    tracer.instant("kv_pages", cat="serve_counter", used=2, total=4,
                   shared_refs=0)
    extra = flight_lib.build_extra_events(
        tracer.events(process_index=0))
    info = trace_mod.export_pod_trace(
        str(tmp_path), process_index=0, process_count=1, tracer=tracer,
        extra_events=extra)
    merged = json.load(open(info["merged_path"]))
    assert merged["metadata"]["counter_events"] == 2
    assert any(e.get("ph") == "C" for e in merged["traceEvents"])


# --------------------------------------------------- scripted ledger


def _req_rec(rid, event, **kw):
    return dict(kind="serve_request", rid=rid, event=event, **kw)


CLEAN_RECORDS = [
    _req_rec(0, res_lib.ADMITTED, slot=0, waited_s=0.005,
             queue_wait_s=0.002, prefill_s=0.003),
    _req_rec(1, res_lib.SHED, queue_depth=6),
    _req_rec(2, res_lib.EXPIRED, waited_s=0.03),
    _req_rec(0, res_lib.DONE, generated=8, e2e_s=0.04, decode_s=0.035),
    _req_rec(3, res_lib.REJECTED, reason="kv_pages_exhausted"),
]

CLEAN_PARTITION = {"arrived": 4, "admitted": 1, "shed_at_admission": 1,
                   "expired_in_queue": 1, "rejected": 1, "completed": 1,
                   "evicted": 0, "lost": 0}


def test_verify_exact_scripted():
    flights = flight_lib.reconstruct(CLEAN_RECORDS)
    res = flight_lib.verify(flights, CLEAN_PARTITION)
    assert res["exact"], res["problems"]
    assert res["flights"] == 4
    assert res["counts"] == CLEAN_PARTITION
    assert res["partition_checked"]
    assert res["decomposed"] == 1
    assert res["ttft_decomp_status"] == slo_lib.SUCCESS
    assert res["ttft_decomp_worst_s"] <= res["ttft_decomp_tol_s"]


def test_verify_flags_every_broken_chain_shape():
    # double admission
    bad = flight_lib.reconstruct(
        [_req_rec(0, res_lib.ADMITTED, waited_s=0.0, queue_wait_s=0.0,
                  prefill_s=0.0),
         _req_rec(0, res_lib.ADMITTED, waited_s=0.0, queue_wait_s=0.0,
                  prefill_s=0.0)])
    r = flight_lib.verify(bad)
    assert not r["exact"] and "admission-stage" in r["problems"][0]
    # admitted but no outcome (dropped on the floor)
    r = flight_lib.verify(flight_lib.reconstruct(
        [_req_rec(0, res_lib.ADMITTED, waited_s=0.0, queue_wait_s=0.0,
                  prefill_s=0.0)]))
    assert not r["exact"] and "0 outcome" in r["problems"][0]
    # events after a terminal shed verdict
    r = flight_lib.verify(flight_lib.reconstruct(
        [_req_rec(0, res_lib.SHED), _req_rec(0, res_lib.DONE)]))
    assert not r["exact"] and "after terminal" in r["problems"][0]
    # decomposition off by more than the pinned tolerance
    r = flight_lib.verify(flight_lib.reconstruct(
        [_req_rec(0, res_lib.ADMITTED, waited_s=0.010,
                  queue_wait_s=0.002, prefill_s=0.003),
         _req_rec(0, res_lib.DONE, generated=2)]))
    assert not r["exact"] and "decomposition" in r["problems"][0]
    assert r["ttft_decomp_status"] == slo_lib.FAIL
    # partition drift is a loud bookkeeping bug
    r = flight_lib.verify(flight_lib.reconstruct(CLEAN_RECORDS),
                          dict(CLEAN_PARTITION, completed=2))
    assert not r["exact"] and "partition mismatch" in r["problems"][0]


def test_verify_tolerance_env_knob(monkeypatch):
    """flight_decomp resolves through the shared rules table — the env
    override every other threshold honors, graded at call time."""
    assert rules_lib.resolve("flight_decomp") \
        == rules_lib.FLIGHT_DECOMP_TOL_S
    recs = [_req_rec(0, res_lib.ADMITTED, waited_s=0.0051,
                     queue_wait_s=0.002, prefill_s=0.003),
            _req_rec(0, res_lib.DONE, generated=2)]
    assert not flight_lib.verify(flight_lib.reconstruct(recs))["exact"]
    monkeypatch.setenv("TPUDIST_SERVE_FLIGHT_TOL_S", "0.001")
    loose = flight_lib.verify(flight_lib.reconstruct(recs))
    assert loose["exact"] and loose["ttft_decomp_tol_s"] == 0.001


def test_trace_cross_check_token_drift_and_drop_skip():
    recs = [_req_rec(0, res_lib.ADMITTED, waited_s=0.005,
                     queue_wait_s=0.002, prefill_s=0.003),
            _req_rec(0, res_lib.DONE, generated=4)]

    def doc(dropped, tokens):
        return {"metadata": {"dropped": dropped}, "traceEvents": [
            {"ph": "X", "cat": "serve", "name": "prefill", "ts": 0.0,
             "dur": 1.0, "pid": 0, "tid": 1, "args": {"rid": 0}},
            {"ph": "X", "cat": "serve", "name": "decode_emit",
             "ts": 2.0, "dur": 0.0, "pid": 0, "tid": 1,
             "args": {"rid": 0, "tokens": tokens}}]}

    good = flight_lib.verify(flight_lib.reconstruct(recs, doc(0, 3)))
    assert good["exact"] and good["trace_checked"] == 1
    drift = flight_lib.verify(flight_lib.reconstruct(recs, doc(0, 2)))
    assert not drift["exact"]
    assert "decode_emit tokens 2" in drift["problems"][0]
    # an overrun ring under-counts the oldest flights: skipping the
    # cross-check is honest, silently passing would not be
    dropped = flight_lib.verify(flight_lib.reconstruct(recs, doc(5, 2)))
    assert dropped["exact"] and dropped["trace_checked"] == 0
    # a slot-track COPY must not double the span evidence
    d = doc(0, 3)
    d["traceEvents"].append(dict(d["traceEvents"][0],
                                 tid=flight_lib.SLOT_TID_BASE,
                                 args={"rid": 0, "track": "slot"}))
    assert flight_lib.verify(flight_lib.reconstruct(recs, d))["exact"]


# -------------------------------------- in-process end-to-end exactness


def _tiny_engine(devices8, **kw):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 16)
    kw.setdefault("prompt_pad", 4)
    kw.setdefault("decode_k", 4)
    return PagedServeEngine(TINY_TF, mesh, **kw), params


def _overload_run(devices8, metrics, *, engine_kw=None,
                  shared_prefix=None, n=40, rate=800.0, prompt_pad=4,
                  prefix_len=0):
    engine, params = _tiny_engine(devices8, **(engine_kw or {}))
    engine.warmup(params)
    requests = sched.make_requests(n, prompt_pad=prompt_pad,
                                   vocab_size=64, max_new=6, rate=rate,
                                   seed=11, prefix_len=prefix_len)
    virtual = res_lib.VirtualTiming(prefill_s=0.002, decode_s=0.004)
    res = res_lib.ResilienceConfig(queue_cap=6, ttft_deadline_s=0.025,
                                   validate=True)
    return sched.run_serve(engine, params, requests, metrics=metrics,
                           resilience=res, virtual=virtual,
                           shared_prefix=shared_prefix)


def test_overloaded_run_flight_ledger_exact(devices8, fresh_tracer):
    """THE tentpole acceptance pin: a seeded overloaded virtual-clock
    run (both shed mechanisms firing) reconstructs to exactly one
    terminal chain per arrived rid, the TTFT decomposition holds at the
    pinned tolerance, the chain counts reconcile BITWISE with the
    ShedLedger partition, and the trace cross-checks (one prefill span
    per admission, decode_emit tokens == generated-1) all hold."""
    m = RecMetrics()
    s = _overload_run(devices8, m)
    assert s["shed_at_admission"] > 0 and s["expired_in_queue"] > 0
    flights = flight_lib.reconstruct(m.recs, _trace_doc(fresh_tracer))
    res = flight_lib.verify(flights, s["partition"])
    assert res["exact"], res["problems"]
    assert res["flights"] == s["arrived"] == 40
    assert res["partition_checked"]
    assert res["trace_checked"] == s["admitted"] > 0
    assert res["decomposed"] == s["admitted"]
    # the trace recorded an arrive instant for every rid too
    arrives = sum(1 for e in fresh_tracer.events(process_index=0)
                  if e["cat"] == "serve" and e["name"] == "arrive")
    assert arrives == s["arrived"]
    # aggregates come out of the same chains
    dc = flight_lib.decomposition(flights)
    assert dc["ttft"]["n"] == s["admitted"]
    assert dc["queue_wait"]["n"] == dc["prefill"]["n"] == s["admitted"]
    tl = flight_lib.shed_timeline(flights)
    assert len(tl) == s["shed_total"]
    ts = [r["t_s"] for r in tl]
    assert ts == sorted(ts)


def test_paged_spec_run_kv_counters_and_slot_tracks(devices8,
                                                    fresh_tracer):
    """The paged + speculative + shared-prefix lane: kv_admit instants
    account granted vs prefix-reused pages, the KV occupancy counter
    samples stay within the pool, decode_emit carries the speculation
    draft/accept split, and the export-time transforms build per-slot
    tracks — with the ledger still exact against the partition."""
    shared = sched.shared_prefix_tokens(8, 64, seed=11)  # = request seed
    m = RecMetrics()
    s = _overload_run(
        devices8, m,
        engine_kw=dict(slots=3, max_seq=32, prompt_pad=16, decode_k=4,
                       page_tokens=8, speculate_k=4),
        shared_prefix=shared, n=24, rate=400.0, prompt_pad=16,
        prefix_len=8)
    assert s["kv_pages_used_peak"] >= 1
    events = fresh_tracer.events(process_index=0)
    admits = [e for e in events if e["name"] == "kv_admit"]
    assert len(admits) == s["admitted"]
    for e in admits:
        a = e["args"]
        assert a["pages"] == a["pages_granted"] + a["shared_pages_reused"]
    # the FIRST shared prefill populates the registry (granted in full);
    # every later admission reuses the 8-token prefix page
    assert sum(e["args"]["shared_pages_reused"] for e in admits) \
        >= len(admits) - 1
    counters = [e for e in events if e["name"] == "kv_pages"]
    assert counters and all(
        0 <= e["args"]["used"] <= e["args"]["total"] for e in counters)
    emits = [e for e in events if e["name"] == "decode_emit"]
    assert emits and all("drafted" in e["args"] and
                         "accepted" in e["args"] for e in emits)
    extra = flight_lib.build_extra_events(events)
    slot_tids = {e["tid"] for e in extra
                 if e.get("ph") != "M"
                 and (e.get("args") or {}).get("track") == "slot"}
    assert slot_tids and all(t >= flight_lib.SLOT_TID_BASE
                             for t in slot_tids)
    assert any(e.get("ph") == "C" and e["name"] == "kv_shared_refs"
               and e["args"]["refs"] >= 1 for e in extra)
    res = flight_lib.verify(
        flight_lib.reconstruct(m.recs, _trace_doc(fresh_tracer)),
        s["partition"])
    assert res["exact"], res["problems"]


def test_trace_off_bitwise_parity_and_zero_clock_reads(devices8,
                                                       monkeypatch):
    """--trace off must be a pure observer toggle: the greedy token
    streams and the whole summary are BITWISE identical either way, and
    the disabled tracer path performs ZERO clock reads."""
    trace_mod.configure(enabled=True)
    try:
        m_on = RecMetrics()
        s_on = _overload_run(devices8, m_on)
    finally:
        tr_off = trace_mod.configure(enabled=False)
    try:
        calls = []
        real = trace_mod._now_ns
        monkeypatch.setattr(trace_mod, "_now_ns",
                            lambda: (calls.append(1), real())[1])
        m_off = RecMetrics()
        s_off = _overload_run(devices8, m_off)
        assert calls == []                 # the disabled path: silent
        assert not tr_off.events(process_index=0)
    finally:
        monkeypatch.undo()
        trace_mod.configure()
    assert s_on == s_off
    assert m_on.recs == m_off.recs


# ------------------------------------------------------- flight CLI


def _run_dir(tmp_path, devices8, tracer):
    m = RecMetrics()
    s = _overload_run(devices8, m)
    with open(tmp_path / "metrics.jsonl", "w") as fh:
        for r in m.recs:
            fh.write(json.dumps(r) + "\n")
        fh.write(json.dumps(dict(
            {k: v for k, v in s.items()
             if k not in ("results", "alert_events", "thresholds")},
            kind="serve", requeue_attempt=0)) + "\n")
    extra = flight_lib.build_extra_events(tracer.events(process_index=0))
    trace_mod.export_pod_trace(str(tmp_path), process_index=0,
                               process_count=1, tracer=tracer,
                               extra_events=extra)
    return s


def test_flight_cli_exits_zero_on_clean_run_dir(tmp_path, devices8,
                                                fresh_tracer, capsys):
    s = _run_dir(tmp_path, devices8, fresh_tracer)
    rc = flight_lib.main(["--run-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "EXACT" in out and f"{s['arrived']} flights" in out
    # and nonzero when a chain breaks (drop one terminal record)
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    done_idx = next(i for i, l in enumerate(lines)
                    if '"event": "done"' in l or "'done'" in l
                    or json.loads(l).get("event") == res_lib.DONE)
    (tmp_path / "metrics.jsonl").write_text(
        "\n".join(lines[:done_idx] + lines[done_idx + 1:]) + "\n")
    assert flight_lib.main(["--run-dir", str(tmp_path)]) == 1


def test_flight_cli_no_artifacts_is_rc2(tmp_path, capsys):
    assert flight_lib.main(["--run-dir", str(tmp_path)]) == 2
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"kind": "timing"}) + "\n")
    assert flight_lib.main(["--run-dir", str(tmp_path)]) == 2


# --------------------------------------------------- report + live views


def test_report_folds_request_flights_section(tmp_path, devices8,
                                              fresh_tracer):
    _run_dir(tmp_path, devices8, fresh_tracer)
    recs = flight_lib.load_metrics(str(tmp_path / "metrics.jsonl"))
    trace_doc = json.load(open(tmp_path / "pod_trace.json"))
    rep = report_lib.build_report(recs, trace_doc)
    assert rep["schema"] == report_lib.REPORT_SCHEMA_VERSION == 8
    fl = rep["flights"]
    assert fl["enabled"] and fl["exact"], fl["problems"]
    assert fl["partition_checked"] and fl["trace_checked"] > 0
    assert fl["decomposition"]["ttft"]["n"] == fl["counts"]["admitted"]
    assert fl["counts"]["shed_at_admission"] > 0
    md = report_lib.to_markdown(rep)
    assert "## Request flights" in md
    assert "ledger exact" in md
    assert "TTFT decomposition success" in md
    # a train-only record stream stays flight-free
    assert report_lib.flights_section([{"kind": "timing"}]) \
        == {"enabled": False}


def test_report_flights_and_paged_fields_fold_jax_blocked(tmp_path):
    """Satellite: the report path folds the paged-serve footprint
    (kv_pages_used_peak, spec_accept_rate) AND the flights section with
    jax blocked — subprocess-pinned like the report's own contract."""
    recs = [dict(kind="serve_request", rid=0, event=res_lib.ADMITTED,
                 t_s=0.01, waited_s=0.005, queue_wait_s=0.002,
                 prefill_s=0.003),
            dict(kind="serve_request", rid=0, event=res_lib.DONE,
                 t_s=0.05, generated=8, e2e_s=0.05, decode_s=0.045),
            dict(kind="serve", requests=1, completed=1,
                 generated_tokens=8, wall_s=0.05,
                 tokens_per_sec_per_chip=40.0, status="success",
                 kv_pages_used_peak=5, kv_pages_total=24,
                 kv_page_tokens=8, spec_accept_rate=0.75,
                 speculate_k=4, requeue_attempt=0,
                 ttft_p50_s=0.005, ttft_p99_s=0.005,
                 itl_p50_s=0.005, itl_p99_s=0.005)]
    (tmp_path / "recs.json").write_text(json.dumps(recs))
    code = (
        "import json, sys\n"
        "sys.modules['jax'] = None\n"
        "sys.modules['jax.numpy'] = None\n"
        "from tpudist.obs import report\n"
        f"recs = json.load(open({str(tmp_path / 'recs.json')!r}))\n"
        "rep = report.build_report(recs, {})\n"
        "sv, fl = rep['serving'], rep['flights']\n"
        "assert sv['kv_pages_used_peak'] == 5, sv\n"
        "assert sv['kv_pages_total'] == 24\n"
        "assert sv['spec_accept_rate'] == 0.75\n"
        "assert sv['speculate_k'] == 4\n"
        "assert fl['enabled'] and fl['exact'], fl\n"
        "assert fl['decomposition']['ttft']['p99_s'] == 0.005\n"
        "assert '## Request flights' in report.to_markdown(rep)\n"
        "print('ok')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_prometheus_serve_histogram_families():
    """The live exporter renders the self-describing per-tick hist
    records as NATIVE histogram families: cumulated le= buckets, +Inf,
    _sum and _count — straight from the record, no raw samples."""
    status = {"run_id": "r", "pod": {"serve": {
        "tokens_per_sec_per_chip": 10.0, "kv_shared_refs": 4,
        "ttft_hist": {"buckets": [0.01, 0.05], "counts": [2, 1, 1],
                      "sum": 0.25, "count": 4},
        "itl_hist": {"buckets": [0.005], "counts": [3, 0],
                     "sum": 0.01, "count": 3},
    }}, "hosts": {}, "alerts": {}, "counters": {}}
    text = live_lib.prometheus_text(status)
    assert "# TYPE tpudist_serve_ttft_seconds histogram" in text
    assert 'tpudist_serve_ttft_seconds_bucket{le="0.01"} 2' in text
    assert 'tpudist_serve_ttft_seconds_bucket{le="0.05"} 3' in text
    assert 'tpudist_serve_ttft_seconds_bucket{le="+Inf"} 4' in text
    assert "tpudist_serve_ttft_seconds_sum 0.25" in text
    assert "tpudist_serve_ttft_seconds_count 4" in text
    assert 'tpudist_serve_itl_seconds_bucket{le="+Inf"} 3' in text
    assert "tpudist_serve_kv_shared_refs 4" in text
    # a malformed hist record renders nothing rather than crashing
    status["pod"]["serve"]["ttft_hist"] = {"buckets": [1], "counts": [1]}
    assert "ttft_seconds_bucket" not in live_lib.prometheus_text(status)


def test_live_ingest_and_render_status_serve_rows(tmp_path):
    """Satellite: the tail dashboard renders the serving pod's vitals —
    previously a serve run tailed as an idle TRAIN pod."""
    tick = dict(kind="serve_tick", t_s=1.0, queue_depth=3,
                active_slots=2, completed=7, generated_tokens=50,
                shed_fraction=0.25, ttft_p99_s=0.02, itl_p99_s=0.004,
                tokens_per_sec_per_chip=12.5, kv_pages_used=5,
                kv_pages_total=24, kv_shared_refs=2,
                spec_accept_rate=0.8,
                ttft_hist={"buckets": [0.01], "counts": [1, 0],
                           "sum": 0.005, "count": 1},
                itl_hist={"buckets": [0.001], "counts": [0, 1],
                          "sum": 0.004, "count": 1})
    agg = live_lib.LiveAggregator(out_dir=str(tmp_path),
                                  start_ticker=False)
    agg.ingest(tick)
    status = agg.snapshot()
    sv = status["pod"]["serve"]
    assert sv["kv_shared_refs"] == 2
    assert sv["ttft_hist"]["count"] == 1
    body = live_lib.render_status(status)
    line = next(l for l in body.splitlines() if l.startswith("serve:"))
    assert "12.50 tok/s/chip" in line
    assert "queue 3" in line and "active 2" in line and "done 7" in line
    assert "shed 25.0%" in line
    assert "kv pages 5/24" in line
    assert "spec accept 80.0%" in line
    agg.close()


# ------------------------------------------------ serve CLI wiring (e2e)


@pytest.mark.slow
def test_serve_cli_traced_e2e_and_trace_off(tmp_path):
    """``python -m tpudist.serve`` on a 4-device CPU mesh exports the
    worker + merged pod trace with per-slot serve tracks and KV
    counters, the flight verifier exits 0 against the run dir, the
    report folds the flights section — and ``--trace off`` writes NO
    trace artifacts while producing bitwise-identical greedy tokens."""
    def run(save_dir, *extra_args):
        env = dict(os.environ)
        env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_PLATFORMS": "cpu",
            "TPUDIST_VERDICT_PATH": str(save_dir / "verdict.txt"),
            "TPUDIST_TTFT_P99_MAX": "120", "TPUDIST_ITL_P99_MAX": "60",
            "TPUDIST_TOKENS_PER_CHIP_MIN": "0.001",
        })
        proc = subprocess.run(
            [sys.executable, "-m", "tpudist.serve", "--requests", "12",
             "--max-new-tokens", "8", "--request-rate", "200",
             "--kv-page-tokens", "8", "--save-dir", str(save_dir),
             *extra_args],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, \
            proc.stderr[-2000:] + proc.stdout[-2000:]
        return proc

    on_dir = tmp_path / "on"
    off_dir = tmp_path / "off"
    on_dir.mkdir(), off_dir.mkdir()
    proc = run(on_dir)
    assert "serve trace ->" in proc.stdout
    assert (on_dir / "trace.worker0.json").exists()
    pod = json.load(open(on_dir / "pod_trace.json"))
    assert pod["metadata"]["counter_events"] > 0
    evs = pod["traceEvents"]
    assert any(e.get("cat") == "serve" and e.get("name") == "prefill"
               for e in evs)
    assert any((e.get("args") or {}).get("track") == "slot"
               for e in evs)
    assert any(e.get("ph") == "C" and e.get("name") == "kv_pages"
               for e in evs)
    verify = subprocess.run(
        [sys.executable, "-m", "tpudist.serve.flight",
         "--run-dir", str(on_dir)],
        capture_output=True, text=True, timeout=120)
    assert verify.returncode == 0, verify.stderr + verify.stdout
    assert "EXACT" in verify.stdout
    recs = flight_lib.load_metrics(str(on_dir / "metrics.jsonl"))
    rep = report_lib.build_report(recs, pod)
    assert rep["flights"]["enabled"] and rep["flights"]["exact"]

    proc_off = run(off_dir, "--trace", "off")
    assert "serve trace ->" not in proc_off.stdout
    assert not (off_dir / "trace.worker0.json").exists()
    assert not (off_dir / "pod_trace.json").exists()

    def tokens(d):
        serve = [r for r in
                 flight_lib.load_metrics(str(d / "metrics.jsonl"))
                 if r.get("kind") == "serve"]
        return serve[-1]["generated_tokens"], serve[-1]["completed"]

    assert tokens(on_dir) == tokens(off_dir)
