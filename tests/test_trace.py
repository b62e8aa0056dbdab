"""The span tracer (tpudist.obs.trace) + offline run report
(tpudist.obs.report): ring-buffer semantics, Chrome trace-event schema,
deterministic clock-offset merging, the report CLI end-to-end, the
zero-overhead-when-disabled pin, and the traced-vs-untraced bitwise
parity of the train CLI.
"""

import json
import os

import pytest

from tpudist import train as train_mod
from tpudist import verdict as verdict_lib
from tpudist.config import TrainConfig, resolve_trace
from tpudist.obs import report as report_mod
from tpudist.obs import trace as trace_mod


# --------------------------------------------------------- ring buffer


class TestRingBuffer:
    def test_wraparound_keeps_newest(self):
        tr = trace_mod.Tracer(capacity=8)
        for i in range(20):
            with tr.span(f"s{i}", cat="t"):
                pass
        assert tr.span_count == 8
        assert tr.dropped == 12
        names = [e["name"] for e in tr.events()]
        assert names == [f"s{i}" for i in range(12, 20)]

    def test_events_chronological_with_partial_fill(self):
        tr = trace_mod.Tracer(capacity=64)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        evs = tr.events()
        assert [e["name"] for e in evs] == [f"s{i}" for i in range(5)]
        assert all(evs[i]["ts"] <= evs[i + 1]["ts"]
                   for i in range(len(evs) - 1))
        assert tr.dropped == 0

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            trace_mod.Tracer(capacity=0)


class TestSpanApis:
    def test_context_manager_and_begin_end_agree(self):
        tr = trace_mod.Tracer(capacity=16)
        with tr.span("cm", cat="a", x=1):
            pass
        h = tr.begin("be", cat="a", x=2)
        tr.end(h)
        evs = tr.events()
        assert [e["name"] for e in evs] == ["cm", "be"]
        for e in evs:
            assert e["ph"] == "X" and e["cat"] == "a"
            assert e["dur"] >= 0 and e["ts"] > 0
        assert evs[0]["args"] == {"x": 1} and evs[1]["args"] == {"x": 2}

    def test_nested_spans_and_open_stack_in_tail(self):
        tr = trace_mod.Tracer(capacity=16)
        with tr.span("outer", cat="t"):
            with tr.span("inner", cat="t"):
                tail = tr.tail()
                # both spans are OPEN here: the stack answers "what
                # phase is this thread in right now"
                assert tail[0]["open"] == ["outer", "inner"]
        evs = tr.events()
        inner = next(e for e in evs if e["name"] == "inner")
        outer = next(e for e in evs if e["name"] == "outer")
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6

    def test_tail_limits_spans_per_thread(self):
        tr = trace_mod.Tracer(capacity=256)
        for i in range(100):
            with tr.span(f"s{i}"):
                pass
        tail = tr.tail(per_thread=64)
        assert len(tail) == 1
        assert len(tail[0]["spans"]) == 64
        assert tail[0]["spans"][-1]["name"] == "s99"
        assert tail[0]["open"] == []

    def test_instant_records_zero_duration(self):
        tr = trace_mod.Tracer(capacity=8)
        tr.instant("mark", cat="t", note="x")
        (e,) = tr.events()
        assert e["dur"] == 0 and e["args"] == {"note": "x"}


# -------------------------------------------- disabled-tracer overhead


class TestDisabledOverhead:
    def test_disabled_span_performs_no_clock_reads(self, monkeypatch):
        """The overhead pin: with tracing off, entering/exiting a span
        must not touch the clock at all — the timed windows the tracer
        instruments (fences, staging waits) see ZERO added syscalls."""
        tr = trace_mod.Tracer(enabled=False)   # ctor samples clock_sync
        calls = []
        real = trace_mod._now_ns
        monkeypatch.setattr(trace_mod, "_now_ns",
                            lambda: (calls.append(1), real())[1])
        with tr.span("x", cat="t"):
            pass
        h = tr.begin("y")
        tr.end(h)
        tr.instant("z")
        assert calls == []
        assert tr.span_count == 0

    def test_disabled_span_is_shared_null(self):
        tr = trace_mod.Tracer(enabled=False)
        assert tr.span("a") is tr.span("b")

    def test_enabled_span_cost_is_microseconds(self):
        """Loose budget pin (~1 µs measured; 100 µs bound absorbs any
        CI-runner noise): recording must stay invisible next to even a
        fast CPU train step."""
        import time
        tr = trace_mod.Tracer(capacity=4096)
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("s", cat="t"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 100e-6, f"{per_span * 1e6:.1f} µs/span"


# ------------------------------------------------- export + merge math


class TestExportSchema:
    def test_chrome_trace_roundtrip(self, tmp_path):
        tr = trace_mod.Tracer(capacity=32)
        with tr.span("outer", cat="init"):
            with tr.span("inner", cat="ckpt", step=3):
                pass
        path = tr.export_local(str(tmp_path / "trace.worker0.json"),
                               process_index=0)
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        meta = doc["metadata"]
        assert meta["schema"] == trace_mod.TRACE_SCHEMA_VERSION
        assert meta["spans"] == 2 and meta["dropped"] == 0
        assert meta["clock_sync"]["wall_ts"] > 0
        pn = [e for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"]
        assert pn[0]["args"]["name"] == "host0"
        spans = report_mod.complete_events(doc)
        assert {e["name"] for e in spans} == {"outer", "inner"}
        for e in spans:
            assert isinstance(e["ts"], float) and isinstance(e["dur"],
                                                             float)
            assert e["pid"] == 0 and isinstance(e["tid"], int)
        assert tr.exported

    def test_merge_shifts_by_scripted_offsets(self):
        """Deterministic clock-offset merge: worker i's timestamps move
        by -offset_ns[i]/1000 µs onto host 0's timeline, pid becomes
        the host index, and metadata carries the offsets."""
        def doc(pid, ts):
            return {"traceEvents": [
                {"ph": "M", "name": "process_name", "pid": pid,
                 "args": {"name": f"host{pid}"}},
                {"name": "work", "cat": "t", "ph": "X", "ts": ts,
                 "dur": 5.0, "pid": pid, "tid": 0}],
                "metadata": {"spans": 1, "dropped": 0}}
        merged = trace_mod.merge_traces(
            [doc(0, 1000.0), doc(1, 1000.0)], [0, 250_000])
        spans = report_mod.complete_events(merged)
        by_pid = {e["pid"]: e for e in spans}
        assert by_pid[0]["ts"] == 1000.0
        assert by_pid[1]["ts"] == 1000.0 - 250.0     # 250 µs shift
        assert merged["metadata"]["clock_offsets_ns"] == [0, 250_000]
        assert merged["metadata"]["hosts"] == 2
        assert merged["metadata"]["spans"] == 2

    def test_offsets_and_gather_single_process(self):
        assert trace_mod.estimate_clock_offsets(1) == [0]
        assert trace_mod._allgather_bytes(b"abc", 1) == [b"abc"]

    def test_export_pod_trace_scripted_two_hosts(self, tmp_path,
                                                 monkeypatch):
        """The multi-host merge path end-to-end with scripted
        collectives (this jax build has no multi-process CPU backend —
        the same stand-in the hoststats tests use): worker 1's payload
        and a +123.456789 ms clock skew arrive via the fake allgather,
        and the merged pod trace must carry both tracks with worker 1
        shifted onto host 0's timeline."""
        import numpy as np
        from jax.experimental import multihost_utils

        OFF_NS = 123_456_789
        other_doc = {
            "traceEvents": [
                {"ph": "M", "name": "process_name", "pid": 1,
                 "args": {"name": "host1"}},
                {"name": "remote_work", "cat": "train", "ph": "X",
                 "ts": 5000.0, "dur": 10.0, "pid": 1, "tid": 0}],
            "metadata": {"spans": 1, "dropped": 0, "process_index": 1}}
        other_payload = json.dumps(other_doc).encode()

        def fake_allgather(x):
            arr = np.asarray(x)
            if arr.dtype == np.int32 and arr.shape == (2,):
                # the clock probe: host1's stamp is OFF_NS later
                stamp = int(arr[0]) * 10**9 + int(arr[1])
                s2 = stamp + OFF_NS
                return np.asarray(
                    [[arr[0], arr[1]], [s2 // 10**9, s2 % 10**9]],
                    np.int32)
            if arr.dtype == np.int32 and arr.shape == (1,):
                return np.asarray([[int(arr[0])],
                                   [len(other_payload)]], np.int32)
            row2 = np.zeros(arr.shape[0], np.uint8)
            row2[:len(other_payload)] = np.frombuffer(other_payload,
                                                      np.uint8)
            return np.stack([arr, row2])

        monkeypatch.setattr(multihost_utils, "sync_global_devices",
                            lambda name: None)
        monkeypatch.setattr(multihost_utils, "process_allgather",
                            fake_allgather)
        tracer = trace_mod.Tracer(capacity=16)
        with tracer.span("local_work", cat="train"):
            pass
        summary = trace_mod.export_pod_trace(
            str(tmp_path), process_index=0, process_count=2,
            tracer=tracer)
        assert summary["clock_offsets_ns"] == [0, OFF_NS]
        merged = json.load(open(tmp_path / "pod_trace.json"))
        assert merged["metadata"]["hosts"] == 2
        assert merged["metadata"]["clock_offsets_ns"] == [0, OFF_NS]
        spans = report_mod.complete_events(merged)
        by_pid = {e["pid"]: e for e in spans}
        assert set(by_pid) == {0, 1}
        # host1's span moved onto host0's timeline: -123456.789 µs
        assert by_pid[1]["ts"] == pytest.approx(5000.0 - OFF_NS / 1e3)
        assert json.load(open(tmp_path / "trace.worker0.json"))


# ------------------------------------------------------ resolve + status


class TestResolveTrace:
    def test_default_on_into_save_dir(self, monkeypatch):
        monkeypatch.delenv("TPUDIST_TRACE", raising=False)
        monkeypatch.delenv("TPUDIST_TRACE_DIR", raising=False)
        cfg = TrainConfig(save_dir="/tmp/sd")
        assert resolve_trace(cfg) == (True, "/tmp/sd")

    def test_env_off_and_dir(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_TRACE", "off")
        monkeypatch.setenv("TPUDIST_TRACE_DIR", "/tmp/td")
        cfg = TrainConfig(save_dir="/tmp/sd")
        assert resolve_trace(cfg) == (False, "/tmp/td")

    def test_flag_beats_env(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_TRACE", "off")
        cfg = TrainConfig(trace="on", trace_dir="/tmp/flag")
        assert resolve_trace(cfg) == (True, "/tmp/flag")

    def test_bad_flag_raises(self):
        with pytest.raises(ValueError):
            resolve_trace(TrainConfig(trace="sometimes"))


class TestTraceStatus:
    def test_off_is_ungateable(self):
        assert verdict_lib.trace_status(
            False, 0, 0, False) == verdict_lib.UNGATEABLE

    def test_exported_with_low_drop_is_success(self):
        assert verdict_lib.trace_status(
            True, 100, 10, True) == verdict_lib.SUCCESS

    def test_export_failure_or_empty_fails(self):
        assert verdict_lib.trace_status(
            True, 100, 0, False) == verdict_lib.FAIL
        assert verdict_lib.trace_status(
            True, 0, 0, True) == verdict_lib.FAIL

    def test_heavy_drop_fails_and_env_threshold(self, monkeypatch):
        assert verdict_lib.trace_status(
            True, 10, 90, True) == verdict_lib.FAIL
        monkeypatch.setenv("TPUDIST_TRACE_DROP_MAX", "0.95")
        assert verdict_lib.trace_status(
            True, 10, 90, True) == verdict_lib.SUCCESS


# ------------------------------------------------- report on a fixture


def _fixture_docs(fence1_s=3.0):
    """Two-host scripted pod trace + metrics: host0 is healthy, host1's
    dispatch fence is ``fence1_s`` long (straggler knob — its epoch
    stretches by the same amount, as a real straggler's would)."""
    S = 1e6     # seconds -> µs

    def host(pid, fence_s):
        return [
            {"name": "epoch", "cat": "train", "ph": "X", "ts": 0.0,
             "dur": (6.0 + fence_s) * S, "pid": pid, "tid": 0},
            {"name": "stage_slab", "cat": "staging", "ph": "X",
             "ts": 1 * S, "dur": 2 * S, "pid": pid, "tid": 0},
            {"name": "slab_wait", "cat": "staging", "ph": "X",
             "ts": 3 * S, "dur": 0.5 * S, "pid": pid, "tid": 0},
            {"name": "fence", "cat": "dispatch", "ph": "X", "ts": 4 * S,
             "dur": fence_s * S, "pid": pid, "tid": 0},
            {"name": "ckpt_enqueue", "cat": "ckpt", "ph": "X",
             "ts": (4.5 + fence_s) * S, "dur": 0.25 * S, "pid": pid,
             "tid": 0},
            {"name": "ckpt_drain", "cat": "ckpt", "ph": "X",
             "ts": (5.0 + fence_s) * S, "dur": 0.75 * S, "pid": pid,
             "tid": 0},
        ]
    trace_doc = {"traceEvents": host(0, 3.0) + host(1, fence1_s),
                 "metadata": {"hosts": 2, "dropped": 0,
                              "clock_offsets_ns": [0, 1000]}}
    metrics = [
        {"kind": "timing", "steps": 100, "run_s": 10.0,
         "compile_warmup_s": 1.0, "staging_status": "success",
         "staging_overlap_fraction": 0.9, "stage_wait_s": 1.0,
         "tuning_status": "ungateable", "trace_status": "success"},
        {"kind": "epoch", "epoch": 0, "avg_loss": 0.5},
        {"kind": "ckpt", "epoch": 0, "enqueue_ms": 250.0},
        {"kind": "ckpt_drain", "drain_ms": 1500.0, "saves": 2},
        {"kind": "hosts", "straggler_status": "fail"},
    ]
    return metrics, trace_doc


class TestReportFixture:
    def test_self_time_subtracts_children(self):
        metrics, doc = _fixture_docs()
        hosts = report_mod.self_times(report_mod.complete_events(doc))
        h0 = hosts[0]
        # epoch(9s) minus its children (2+0.5+3+0.25+0.75 = 6.5s)
        assert h0["phases"]["train"] == pytest.approx(2.5, rel=1e-6)
        assert h0["phases"]["staging"] == pytest.approx(2.5, rel=1e-6)
        assert h0["phases"]["dispatch"] == pytest.approx(3.0, rel=1e-6)
        assert h0["phases"]["ckpt"] == pytest.approx(1.0, rel=1e-6)
        # phase totals sum EXACTLY to the covered wall (proper nesting)
        assert sum(h0["phases"].values()) == pytest.approx(9.0)
        assert h0["coverage"] == pytest.approx(1.0)

    def test_straggler_attribution_names_the_phase(self):
        metrics, doc = _fixture_docs(fence1_s=5.5)
        rep = report_mod.build_report(metrics, doc)
        att = rep["stragglers"]["attribution"]
        assert att and att[0]["process"] == 1
        assert att[0]["phase"] == "dispatch"
        assert att[0]["excess_s"] == pytest.approx(1.25, abs=1e-6)
        assert rep["stragglers"]["status"] == "fail"
        assert rep["verdict"] == "fail"      # straggler fail bubbles up

    def test_staging_and_ckpt_sections(self):
        metrics, doc = _fixture_docs()
        rep = report_mod.build_report(metrics, doc)
        st = rep["staging"]
        assert st["exposed_wait_s"] == pytest.approx(1.0)   # 2 hosts
        assert st["stage_host_s"] == pytest.approx(4.0)
        assert st["slabs"] == 2
        ck = rep["ckpt"]
        assert ck["drain_s"] == pytest.approx(1.5)
        assert ck["enqueue_s"] == pytest.approx(0.5)
        assert ck["worst_drain_s"] == pytest.approx(0.75)
        assert ck["timing_drain_ms"] == 1500.0

    def test_regression_gate(self):
        metrics, doc = _fixture_docs()
        rep = report_mod.build_report(metrics, doc,
                                      baseline={"steps_per_sec": 10.0})
        assert rep["regression"]["status"] == "success"
        assert rep["regression"]["ratio"] == pytest.approx(1.0)
        rep = report_mod.build_report(metrics, doc,
                                      baseline={"steps_per_sec": 100.0})
        assert rep["regression"]["status"] == "fail"
        assert rep["verdict"] == "fail"
        rep = report_mod.build_report(metrics, doc)
        assert rep["regression"]["status"] == "ungateable"

    def test_markdown_renders(self):
        metrics, doc = _fixture_docs()
        md = report_mod.to_markdown(report_mod.build_report(metrics, doc))
        assert "# tpudist run report" in md
        assert "host0" in md and "host1" in md
        assert "Staging" in md and "Checkpointing" in md


# --------------------------------------------- train CLI end to end


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced CPU train run shared by the e2e assertions below."""
    save = tmp_path_factory.mktemp("traced_run")
    rc = train_mod.main(["--epochs", "2", "--train-batch-size", "64",
                         "--n-samples", "512", "--log-every", "4",
                         "--save-dir", str(save)])
    assert rc == 0
    return save


def test_traced_run_exports_pod_trace(traced_run):
    doc = json.load(open(traced_run / "pod_trace.json"))
    assert json.load(open(traced_run / "trace.worker0.json"))
    spans = report_mod.complete_events(doc)
    names = {e["name"] for e in spans}
    # the phase categories the tentpole promises: staging, dispatch and
    # checkpoint phases are all present as spans, one track per host
    assert {"stage_slab", "dispatch", "fence", "epoch",
            "ckpt_enqueue", "ckpt_drain"} <= names
    assert {e["pid"] for e in spans} == {0}
    t = [json.loads(ln) for ln in open(traced_run / "metrics.jsonl")]
    timing = [r for r in t if r["kind"] == "timing"][0]
    assert timing["trace_status"] == verdict_lib.SUCCESS
    assert timing["trace_spans"] == doc["metadata"]["spans"]
    assert all("mono" in r for r in t)    # monotonic ts on every record


def test_report_cli_end_to_end(traced_run, capsys):
    rc = report_mod.main(["--run-dir", str(traced_run)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run report" in out
    rep = json.load(open(traced_run / "run_report.json"))
    md = (traced_run / "run_report.md").read_text()
    assert "# tpudist run report" in md
    # ACCEPTANCE PIN: per-phase self-time totals cover >= 90% of the
    # host's traced wall time (the merged timeline explains the run,
    # not a sample of it)
    h0 = rep["hosts"]["0"]
    assert h0["coverage"] >= 0.9, h0
    assert {"init", "train", "dispatch"} <= set(h0["phases"])
    assert rep["run"]["steps_per_sec"] > 0
    assert rep["verdict"] == "success"


def test_report_cli_regression_against_self_baseline(traced_run,
                                                     tmp_path):
    rep = json.load(open(traced_run / "run_report.json"))
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps(
        {"steps_per_sec": rep["run"]["steps_per_sec"]}))
    rc = report_mod.main(["--run-dir", str(traced_run),
                          "--baseline", str(base),
                          "--out-json", str(tmp_path / "r.json"),
                          "--out-md", str(tmp_path / "r.md")])
    assert rc == 0
    rep2 = json.load(open(tmp_path / "r.json"))
    assert rep2["regression"]["status"] == "success"
    # an absurd baseline must flag the regression and exit nonzero
    base.write_text(json.dumps(
        {"steps_per_sec": rep["run"]["steps_per_sec"] * 100}))
    rc = report_mod.main(["--run-dir", str(traced_run),
                          "--baseline", str(base),
                          "--out-json", str(tmp_path / "r.json"),
                          "--out-md", str(tmp_path / "r.md")])
    assert rc == 1
    rep3 = json.load(open(tmp_path / "r.json"))
    assert rep3["regression"]["status"] == "fail"
    assert rep3["verdict"] == "fail"


def test_report_cli_missing_inputs(tmp_path, capsys):
    assert report_mod.main(["--run-dir", str(tmp_path)]) == 2
    assert "missing" in capsys.readouterr().err


def test_trace_off_is_bitwise_identical_and_artifact_free(traced_run,
                                                          tmp_path):
    """The acceptance pin: --trace off removes every artifact and every
    timed-window syscall, and the per-step losses match the traced run
    BITWISE (tracing is host-side only — device math untouched)."""
    save = tmp_path / "untraced"
    rc = train_mod.main(["--epochs", "2", "--train-batch-size", "64",
                         "--n-samples", "512", "--log-every", "4",
                         "--trace", "off", "--save-dir", str(save)])
    assert rc == 0
    assert not (save / "pod_trace.json").exists()
    assert not (save / "trace.worker0.json").exists()

    def step_losses(p):
        return [(r["step"], r["loss"]) for r in
                (json.loads(ln) for ln in open(p / "metrics.jsonl"))
                if r["kind"] == "step"]
    assert step_losses(save) == step_losses(traced_run)
    t = [json.loads(ln) for ln in open(save / "metrics.jsonl")
         if '"timing"' in ln][0]
    assert t["trace_status"] == verdict_lib.UNGATEABLE


# ------------------------------------------------ flightrec integration


def test_stall_dump_carries_span_tail_and_local_trace(tmp_path):
    """Satellite: a stall dump shows WHAT PHASE each thread was in (the
    open-span stack + buffer tail) and exports the local timeline so a
    hung run still leaves a loadable trace."""
    import time

    from tpudist.metrics import MetricsLogger
    from tpudist.obs import FlightRecorder

    tracer = trace_mod.Tracer(capacity=128)
    with tracer.span("warm", cat="train"):
        pass
    metrics = MetricsLogger(path=None)
    rec = FlightRecorder(str(tmp_path), stall_timeout_s=0.3,
                         metrics=metrics, tracer=tracer)
    try:
        rec.note_progress(phase="train", epoch=0, step=3)
        with tracer.span("wedged_phase", cat="dispatch"):
            deadline = time.monotonic() + 10.0
            while rec.dumps < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
        assert rec.dumps >= 1
    finally:
        rec.close()
        metrics.close()
    art = json.load(open(rec.flightrec_path))
    assert art["spans"], "stall dump must embed the span-buffer tail"
    main_thread = art["spans"][0]
    assert "wedged_phase" in main_thread["open"]
    assert any(s["name"] == "warm" for s in main_thread["spans"])
    # the local Chrome trace landed next to the flight record
    local = json.load(open(tmp_path / "trace.worker0.json"))
    assert report_mod.complete_events(local)
