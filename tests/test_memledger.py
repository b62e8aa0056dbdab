"""HBM memory ledger (tpudist.obs.memledger): exact per-bucket
attribution of one device's HBM. The scripted tests pin the partition
math (sum == device HBM always, residue only against a real device
watermark, negative headroom honest not inexact); the consumer tests
pin the kind=memledger record, the live gauges + hbm_headroom alert,
the schema-8 report Memory section and the Prometheus textfile against
the SAME ledger; the forensics tests reconstruct the guilty bucket
from artifacts alone (the scripted OOM drill included); the e2e tests
run the real train and paged-serve CLIs on the CPU mesh and pin the
exact partition plus the ledger-informed staging budget's bitwise
loss-neutrality.
"""

import json
import os
import subprocess
import sys

import pytest

from tpudist import rules as rules_lib
from tpudist import verdict as verdict_lib
from tpudist.obs import memledger as ml
from tpudist.obs import report as report_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- the gate


def test_headroom_status_three_valued(monkeypatch):
    assert ml.hbm_headroom_status(None) == ml.UNGATEABLE
    assert ml.hbm_headroom_status(0.2) == ml.SUCCESS
    assert ml.hbm_headroom_status(-0.01) == ml.FAIL
    assert ml.hbm_headroom_status(rules_lib.HBM_HEADROOM_MIN) \
        == ml.SUCCESS
    # env override read at CALL time, like every other gate
    monkeypatch.setenv("TPUDIST_HBM_HEADROOM_MIN", "0.3")
    assert ml.hbm_headroom_status(0.2) == ml.FAIL
    # explicit floor wins
    assert ml.hbm_headroom_status(0.2, 0.1) == ml.SUCCESS


def test_gate_shares_the_rules_constant():
    """One constant, three aliases — the graders cannot drift (the
    shared-rules pin every gate carries)."""
    assert ml.HBM_HEADROOM_MIN is rules_lib.HBM_HEADROOM_MIN
    assert verdict_lib.HBM_HEADROOM_MIN is rules_lib.HBM_HEADROOM_MIN
    assert rules_lib.get("hbm_headroom").sense == "min"
    assert rules_lib.get("hbm_headroom").alert is True
    assert verdict_lib.hbm_headroom_status(0.4) \
        == ml.hbm_headroom_status(0.4)
    # default floor 0.0: only an over-committed device fails unopted
    assert rules_lib.resolve("hbm_headroom") == 0.0


# ------------------------------------------------- the partition math


def scripted_ledger(**kw):
    base = dict(total_hbm_bytes=1000, params_bytes=100,
                opt_state_bytes=200, slab_bytes=50,
                programs={"train_step": {"temp_bytes": 30,
                                         "generated_code_bytes": 20}},
                watermark_bytes=401, watermark_source="memory_stats",
                mode="train", run_id="r1")
    base.update(kw)
    return ml.build_ledger(**base)


def test_partition_sums_to_total_by_construction():
    led = scripted_ledger()
    b = led["buckets"]
    # THE invariant: the seven buckets sum to device HBM, exactly
    assert sum(b.values()) == led["total_hbm_bytes"] == 1000
    assert b["params"] == 100 and b["opt_state"] == 200
    assert b["slabs"] == 50 and b["kv_pool"] == 0
    assert b["program_temp"] == 50          # temp 30 + generated 20
    assert b["residue"] == 1                # watermark 401 - derived 400
    assert b["headroom"] == 599
    assert led["headroom_fraction"] == pytest.approx(0.599)
    assert led["exact"] is True and led["problems"] == []
    assert led["headroom_status"] == ml.SUCCESS
    assert led["run_id"] == "r1" and led["mode"] == "train"


def test_rss_watermark_never_reconciles():
    """An RSS fallback watermark measures the HOST, not the device
    partition: residue stays 0 no matter how far off it is."""
    led = scripted_ledger(watermark_bytes=900, watermark_source="rss")
    assert led["buckets"]["residue"] == 0
    assert led["buckets"]["headroom"] == 600
    assert led["exact"] is True and led["problems"] == []
    # and so does no watermark at all
    led2 = scripted_ledger(watermark_bytes=None, watermark_source=None)
    assert led2["buckets"]["residue"] == 0
    assert sum(led2["buckets"].values()) == 1000


def test_residue_past_tolerance_flags_inexact_both_directions():
    # watermark far ABOVE derived: unattributed allocations
    led = scripted_ledger(watermark_bytes=600)
    assert led["buckets"]["residue"] == 200
    assert led["exact"] is False
    assert any("unattributed" in p for p in led["problems"])
    # the sum STILL equals the total — exactness is about honesty,
    # not about forcing the numbers (the goodput discipline)
    assert sum(led["buckets"].values()) == 1000
    # derived far above watermark: double counting, residue negative
    led2 = scripted_ledger(watermark_bytes=100)
    assert led2["buckets"]["residue"] == -300
    assert led2["exact"] is False
    assert any("double counting" in p for p in led2["problems"])
    assert sum(led2["buckets"].values()) == 1000
    # inside the pinned 1% stays exact
    led3 = scripted_ledger(watermark_bytes=409)
    assert led3["exact"] is True and led3["buckets"]["residue"] == 9


def test_negative_headroom_is_honest_note_and_default_fail():
    """Over-commit is NOT an accounting error: the partition stays
    exact with headroom honestly negative — and the default 0.0 floor
    breaches on exactly this with no opt-in."""
    led = scripted_ledger(params_bytes=2000, watermark_bytes=None,
                          watermark_source=None)
    assert led["buckets"]["headroom"] < 0
    assert sum(led["buckets"].values()) == 1000
    assert led["exact"] is True
    assert any("over-committed" in n for n in led["notes"])
    assert led["headroom_status"] == ml.FAIL


def test_program_temp_is_max_not_sum():
    """Programs never run concurrently on one device: peak scratch is
    the MAX of per-program temp + generated code, not the sum."""
    programs = {
        "prefill": {"temp_bytes": 100, "generated_code_bytes": 10},
        "decode_k8": {"temp_bytes": 60, "generated_code_bytes": 80},
        "verify": {"temp_bytes": 5},
    }
    peak, complete = ml.program_temp_bytes(programs)
    assert peak == 140 and complete is True
    # a program with no analysis under-counts: complete False, and the
    # ledger records it as a NOTE, never a problem (CPU backends may
    # not implement memory planning — CI must still be green)
    programs["decode_k16"] = {}
    peak2, complete2 = ml.program_temp_bytes(programs)
    assert peak2 == 140 and complete2 is False
    led = scripted_ledger(programs=programs, watermark_bytes=None,
                          watermark_source=None)
    assert led["program_temp_complete"] is False
    assert led["exact"] is True and led["problems"] == []
    assert any("decode_k16" in n for n in led["notes"])
    assert ml.program_temp_bytes(None) == (0, True)


def test_negative_bucket_is_a_problem_and_clamped():
    led = scripted_ledger(slab_bytes=-5, watermark_bytes=None,
                          watermark_source=None)
    assert led["exact"] is False
    assert any("negative" in p for p in led["problems"])
    assert led["buckets"]["slabs"] == 0
    assert sum(led["buckets"].values()) == 1000


def test_total_hbm_must_be_positive():
    with pytest.raises(ValueError, match="TPUDIST_HBM_BYTES"):
        ml.build_ledger(total_hbm_bytes=0)


def test_record_round_trip():
    led = scripted_ledger()
    rec = ml.ledger_record(led)
    assert rec["params_bytes"] == 100 and rec["headroom_bytes"] == 599
    assert rec["hbm_headroom_status"] == led["headroom_status"]
    back = ml.from_record(rec)
    assert back["buckets"] == led["buckets"]
    assert back["total_hbm_bytes"] == 1000
    assert back["headroom_fraction"] == led["headroom_fraction"]
    assert back["exact"] is True
    # a record with no bucket bytes at all is not a ledger
    assert ml.from_record({"kind": "memledger"}) is None


# ----------------------------------------------------------- forensics


def _write_run_dir(tmp_path, *, kv_growth=0, flight_reason=None):
    """A scripted run dir: one kind=memledger record (the baseline),
    the memledger.json artifact, and optionally a flight record whose
    embedded ledger grew kv_pool — the pre-mortem state."""
    base = scripted_ledger(kv_pool_bytes=100, watermark_bytes=None,
                           watermark_source=None)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"kind": "step", "step": 1}) + "\n")
        f.write(json.dumps(dict(kind="memledger",
                                **ml.ledger_record(base))) + "\n")
    (tmp_path / ml.LEDGER_NAME).write_text(json.dumps(base))
    (tmp_path / "trace.worker0.json").write_text(
        json.dumps({"traceEvents": []}))
    if flight_reason is not None:
        death = json.loads(json.dumps(base))
        death["buckets"]["kv_pool"] += kv_growth
        death["buckets"]["headroom"] -= kv_growth
        (tmp_path / "flightrec.worker0").write_text(json.dumps(
            {"reason": flight_reason,
             "extra": {"memledger": death}}))
    return base


def test_collect_ledgers_evidence_order(tmp_path):
    _write_run_dir(tmp_path, kv_growth=700,
                   flight_reason="RESOURCE_EXHAUSTED: out of memory")
    pairs = ml.collect_ledgers(str(tmp_path))
    assert [src for src, _ in pairs] == \
        ["metrics.jsonl", ml.LEDGER_NAME, "flightrec.worker0"]
    # a .tmp flight record is never evidence
    (tmp_path / "flightrec.worker1.tmp").write_text("{}")
    assert len(ml.collect_ledgers(str(tmp_path))) == 3


def test_diagnose_names_the_grown_bucket_and_knob(tmp_path):
    _write_run_dir(tmp_path, kv_growth=700,
                   flight_reason="RESOURCE_EXHAUSTED: allocating 1.2G")
    diag = ml.diagnose(str(tmp_path))
    assert diag["oom"] is True
    assert "RESOURCE_EXHAUSTED" in diag["reason"]
    assert diag["guilty_bucket"] == "kv_pool"
    assert diag["growth"]["kv_pool"] == 700
    assert diag["knob"] == ml.KNOBS["kv_pool"]
    assert diag["death_source"] == "flightrec.worker0"
    lines = ml.forensics_lines(diag)
    assert any("OOM death detected" in ln for ln in lines)
    assert any("guilty bucket: kv_pool" in ln for ln in lines)
    assert any("--kv-pages" in ln for ln in lines)


def test_diagnose_single_snapshot_names_largest_bucket(tmp_path):
    base = scripted_ledger(watermark_bytes=None, watermark_source=None)
    (tmp_path / ml.LEDGER_NAME).write_text(json.dumps(base))
    diag = ml.diagnose(str(tmp_path))
    assert diag["oom"] is False and diag["ledgers"] == 1
    assert diag["guilty_bucket"] == "opt_state"   # largest attributed
    assert diag["growth"] == {} and diag["baseline_source"] is None
    lines = ml.forensics_lines(diag)
    assert any("largest attributed bucket" in ln for ln in lines)


def test_cli_no_evidence_exits_2(tmp_path, capsys):
    assert ml.main(["--run-dir", str(tmp_path)]) == 2
    assert "no ledger evidence" in capsys.readouterr().err


def test_cli_inexact_partition_exits_1(tmp_path, capsys):
    led = scripted_ledger(watermark_bytes=600)       # unattributed
    (tmp_path / ml.LEDGER_NAME).write_text(json.dumps(led))
    assert ml.main(["--run-dir", str(tmp_path)]) == 1
    assert "INEXACT" in capsys.readouterr().out


def test_cli_baseline_delta_and_unreadable_baseline(tmp_path, capsys):
    _write_run_dir(tmp_path)
    old = tmp_path / "old.json"
    old.write_text(json.dumps(scripted_ledger(
        kv_pool_bytes=40, watermark_bytes=None, watermark_source=None)))
    assert ml.main(["--run-dir", str(tmp_path),
                    "--baseline", str(old)]) == 0
    out = capsys.readouterr().out
    assert "delta vs baseline" in out
    assert ml.main(["--run-dir", str(tmp_path),
                    "--baseline", str(tmp_path / "nope.json")]) == 2


# ------------------------------------------------ prometheus + bench


GOLDEN_PROM = """\
# HELP tpudist_memledger_info Ledger identity (labels carry mode and \
exactness).
# TYPE tpudist_memledger_info gauge
tpudist_memledger_info{mode="train",exact="true"} 1
# HELP tpudist_hbm_bytes Per-device HBM bytes per ledger bucket (the \
partition sums to device HBM).
# TYPE tpudist_hbm_bytes gauge
tpudist_hbm_bytes{bucket="params"} 100
tpudist_hbm_bytes{bucket="opt_state"} 200
tpudist_hbm_bytes{bucket="slabs"} 50
tpudist_hbm_bytes{bucket="kv_pool"} 0
tpudist_hbm_bytes{bucket="program_temp"} 50
tpudist_hbm_bytes{bucket="headroom"} 599
tpudist_hbm_bytes{bucket="residue"} 1
# HELP tpudist_hbm_total_bytes Device HBM size the ledger partitions.
# TYPE tpudist_hbm_total_bytes gauge
tpudist_hbm_total_bytes 1000
# HELP tpudist_hbm_headroom_fraction Unattributed free fraction of \
device HBM.
# TYPE tpudist_hbm_headroom_fraction gauge
tpudist_hbm_headroom_fraction 0.599
# HELP tpudist_memledger_exact 1 when the watermark reconciliation \
met the pinned tolerance.
# TYPE tpudist_memledger_exact gauge
tpudist_memledger_exact 1
"""


def test_prometheus_text_golden():
    assert ml.prometheus_text(scripted_ledger()) == GOLDEN_PROM


def test_bench_artifact_shape():
    led = scripted_ledger()
    art = ml.bench_artifact(led, extra_detail={"rows": [1, 2]})
    assert art["metric"] == "hbm_headroom_fraction"
    assert art["value"] == led["headroom_fraction"]
    assert art["detail"]["ledger"] is led
    assert art["detail"]["rows"] == [1, 2]


# ---------------------------------------------- live gauges + alert


def test_live_ingests_memledger_and_renders_gauges(tmp_path,
                                                   monkeypatch):
    from tpudist.obs import live as live_lib
    monkeypatch.setenv("TPUDIST_HBM_HEADROOM_MIN", "0.7")
    agg = live_lib.LiveAggregator(out_dir=str(tmp_path),
                                  start_ticker=False)
    rec = dict(kind="memledger", **ml.ledger_record(scripted_ledger()))
    agg.ingest(rec)
    snap = agg.snapshot()
    got = snap["pod"]["memledger"]
    assert got["buckets"]["params"] == 100
    assert got["buckets"]["headroom"] == 599
    assert got["total_hbm_bytes"] == 1000
    assert got["exact"] is True
    text = live_lib.prometheus_text(snap)
    assert 'tpudist_hbm_bytes{bucket="params"} 100' in text
    assert 'tpudist_hbm_bytes{bucket="headroom"} 599' in text
    assert "tpudist_hbm_total_bytes 1000" in text
    assert "tpudist_hbm_headroom_fraction 0.599" in text
    assert "tpudist_memledger_exact 1" in text
    # 0.599 headroom under the 0.7 opt-in floor: the alert fires
    assert {a["alert"] for a in agg.engine.firing()} == {"hbm_headroom"}
    # no ledger ingested -> none of the gauges render (the golden
    # dense exposition stays safe)
    agg2 = live_lib.LiveAggregator(out_dir=str(tmp_path / "d"),
                                   start_ticker=False)
    agg2.ingest({"kind": "step", "step": 1, "loss": 0.5})
    text2 = live_lib.prometheus_text(agg2.snapshot())
    assert "tpudist_hbm_" not in text2
    assert not agg2.engine.firing()


# -------------------------------------------------- report section


def test_report_memory_section_from_artifact_and_record():
    led = scripted_ledger()
    sec = report_lib.memory_section([], led)
    assert sec["enabled"] and sec["status"] == ml.SUCCESS
    assert sec["headroom_fraction"] == led["headroom_fraction"]
    assert sec["buckets"]["opt_state"] == 200
    assert sec["programs"] == ["train_step"]
    assert sec["exact"] is True
    # no artifact: the last kind=memledger record carries the section
    metrics = [{"kind": "step"},
               dict(kind="memledger", **ml.ledger_record(led))]
    sec2 = report_lib.memory_section(metrics)
    assert sec2["enabled"] and sec2["buckets"] == sec["buckets"]
    # no evidence at all: disabled + ungateable, never a crash
    empty = report_lib.memory_section([])
    assert empty == {"enabled": False,
                     "status": report_lib.UNGATEABLE}


def test_report_memory_delta_vs_baseline():
    led = scripted_ledger(kv_pool_bytes=300)
    base = scripted_ledger(kv_pool_bytes=100)
    sec = report_lib.memory_section([], led, baseline=base)
    assert sec["bucket_delta_bytes"]["kv_pool"] == 200
    assert sec["bucket_delta_bytes"]["params"] == 0
    # a prior run_report's memory section works as a baseline too
    sec2 = report_lib.memory_section(
        [], led, baseline={"memory": {"buckets": base["buckets"]}})
    assert sec2["bucket_delta_bytes"]["kv_pool"] == 200


def test_report_memory_regrades_at_fold_time(monkeypatch):
    led = scripted_ledger()                  # 59.9% headroom
    monkeypatch.setenv("TPUDIST_HBM_HEADROOM_MIN", "0.9")
    sec = report_lib.memory_section([], led)
    assert sec["status"] == ml.FAIL and sec["min_fraction"] == 0.9


def test_report_schema_mirror_matches_the_real_constant():
    assert report_lib.KNOWN_ARTIFACT_SCHEMAS["memledger"] \
        is ml.MEMLEDGER_SCHEMA_VERSION
    assert report_lib.REPORT_SCHEMA_VERSION >= 8


def test_report_warns_newer_memledger_schema_and_still_folds(
        tmp_path, capsys):
    led = scripted_ledger()
    led["schema"] = 99
    (tmp_path / ml.LEDGER_NAME).write_text(json.dumps(led))
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"kind": "step", "step": 1}) + "\n")
    (tmp_path / "trace.worker0.json").write_text(
        json.dumps({"traceEvents": []}))
    rc = report_lib.main(["--run-dir", str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 0
    assert "memledger artifact carries schema" in err
    rep = json.load(open(tmp_path / "run_report.json"))
    assert rep["schema"] == report_lib.REPORT_SCHEMA_VERSION
    assert rep["memory"]["enabled"], "newer ledger must still fold"
    md = open(tmp_path / "run_report.md").read()
    assert "## Memory" in md
    # an explicit --memledger path that does not exist is exit 2
    assert report_lib.main(["--run-dir", str(tmp_path), "--memledger",
                            str(tmp_path / "nope.json")]) == 2


def test_report_older_run_dir_folds_ungateable(tmp_path):
    """A pre-ledger run dir (no memledger.json, no kind=memledger
    record) folds gracefully: Memory disabled, report green."""
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"kind": "step", "step": 1, "loss": 0.5}) + "\n")
    (tmp_path / "trace.worker0.json").write_text(
        json.dumps({"traceEvents": []}))
    rc = report_lib.main(["--run-dir", str(tmp_path)])
    assert rc == 0
    rep = json.load(open(tmp_path / "run_report.json"))
    assert rep["memory"] == {"enabled": False,
                             "status": report_lib.UNGATEABLE}


# -------------------------------------------------- consumer parity


def test_cli_report_and_prometheus_agree_on_the_buckets(tmp_path,
                                                        capsys):
    """The consumer-parity pin: the memledger CLI, the schema-8 report
    Memory section and the Prometheus textfile carry the IDENTICAL
    bucket bytes and headroom fraction."""
    _write_run_dir(tmp_path)
    rc = ml.main(["--run-dir", str(tmp_path),
                  "--bench-out", str(tmp_path / "BENCH_MEMORY.json"),
                  "--prom-out", str(tmp_path / "memledger.prom")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "tpudist: memledger" in out and "partition exact" in out
    led = json.load(open(tmp_path / ml.LEDGER_NAME))
    frac = led["headroom_fraction"]
    rc = report_lib.main(["--run-dir", str(tmp_path)])
    assert rc == 0
    rep = json.load(open(tmp_path / "run_report.json"))
    assert rep["memory"]["enabled"]
    assert rep["memory"]["headroom_fraction"] == frac
    assert rep["memory"]["buckets"] == led["buckets"]
    prom = open(tmp_path / "memledger.prom").read()
    line = [ln for ln in prom.splitlines()
            if ln.startswith("tpudist_hbm_headroom_fraction ")][0]
    assert float(line.split()[-1]) == frac
    bench = json.load(open(tmp_path / "BENCH_MEMORY.json"))
    assert bench["value"] == frac
    md = open(tmp_path / "run_report.md").read()
    assert "## Memory" in md and "| params |" in md


def test_memledger_cli_is_jax_free(tmp_path):
    """The offline-tooling contract (shared with obs.report and
    obs.goodput): forensics run with jax import-blocked — a CI host or
    laptop with nothing but the stdlib against scp'd artifacts."""
    _write_run_dir(tmp_path, kv_growth=700,
                   flight_reason="RESOURCE_EXHAUSTED: oom")
    code = ("import sys; sys.modules['jax'] = None; "
            "from tpudist.obs import memledger; "
            f"rc = memledger.main(['--run-dir', {str(tmp_path)!r}, "
            f"'--prom-out', {str(tmp_path / 'm.prom')!r}]); "
            "assert rc == 0, rc; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr
    assert "guilty bucket: kv_pool" in out.stdout


# ------------------------------------------------------ the drill


def test_drill_forensics_names_the_grown_bucket(tmp_path, capsys):
    """THE OOM acceptance drill, scripted end: a real baseline ledger
    in the run dir, the drill grows one bucket past headroom and dumps
    the flight record an OOM death leaves — the CLI must reconstruct
    the guilty bucket and name its knob from artifacts alone."""
    base = scripted_ledger(watermark_bytes=None, watermark_source=None)
    (tmp_path / ml.LEDGER_NAME).write_text(json.dumps(base))
    rc = ml.main(["--drill", "--drill-grow", "kv_pool",
                  "--run-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "OOM death detected" in out
    assert "guilty bucket: kv_pool" in out
    assert ml.KNOBS["kv_pool"].split(" ")[0] in out
    fr = json.loads((tmp_path / "flightrec.worker0").read_text())
    assert fr["reason"] == ml.DRILL_REASON
    death = fr["extra"]["memledger"]
    # the synthetic pre-mortem state keeps the partition exact and
    # honestly over-committed
    assert sum(death["buckets"].values()) == death["total_hbm_bytes"]
    assert death["buckets"]["headroom"] < 0
    assert death["headroom_status"] == ml.FAIL
    # a dir with no baseline ledger refuses the drill loudly
    with pytest.raises(RuntimeError, match="no baseline ledger"):
        ml.run_drill(str(tmp_path / "empty"))


# --------------------------------------- allocator memory bound


def _paged_spec(**kw):
    from tpudist.config import ModelConfig
    from tpudist.serve import kvcache
    cfg = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      max_seq_len=64)
    base = dict(slots=4, max_seq=64, page_tokens=8, pages=32,
                dtype="float32")
    base.update(kw)
    return kvcache.PagedCacheSpec.from_model(cfg, **base)


def test_set_memory_bound_ledger_vs_heuristic():
    from tpudist.config import STAGING_STATE_HEADROOM
    from tpudist.serve import kvcache
    spec = _paged_spec()
    page_bytes = 2 * spec.n_layers * spec.page_tokens \
        * spec.n_kv_heads * spec.head_dim * 4
    alloc = kvcache.PageAllocator(spec)
    assert alloc.page_cap == spec.pages and alloc.bound_source == "none"
    params = 10 * page_bytes
    hbm = 20 * page_bytes + spec.table_bytes
    # ledger path: margin = params + measured temp
    cap = alloc.set_memory_bound(hbm_bytes=hbm, params_bytes=params,
                                 program_temp_bytes=2 * page_bytes)
    assert alloc.bound_source == "ledger" and cap == 8
    # heuristic path: margin = 4x params — strictly tighter here
    alloc2 = kvcache.PageAllocator(spec)
    cap2 = alloc2.set_memory_bound(hbm_bytes=hbm, params_bytes=params)
    assert alloc2.bound_source == "heuristic"
    assert cap2 == max(int(20 - STAGING_STATE_HEADROOM * 10), 0)
    assert cap > cap2, "measured scratch must beat the 4x guess here"
    # the cap clamps to the pool and never goes negative
    assert alloc2.set_memory_bound(hbm_bytes=0, params_bytes=params) == 0
    assert alloc2.set_memory_bound(hbm_bytes=1e15,
                                   params_bytes=0) == spec.pages


def test_page_cap_backpressures_admission_and_reject():
    from tpudist.serve import kvcache
    spec = _paged_spec()
    alloc = kvcache.PageAllocator(spec)
    alloc.page_cap = 3
    # within the cap: pages map; at the cap: backpressure, rollback
    assert alloc.admit(0, 24)                 # 3 pages
    assert alloc.pages_used() == 3
    assert not alloc.admit(1, 8)              # cap hit -> False
    assert alloc.pages_used() == 3
    # structurally unservable at the cap: reject, don't wait forever
    assert not alloc.can_ever_admit(32, shared=False)   # needs 4 > 3
    assert alloc.can_ever_admit(24, shared=False)
    alloc.free_slot(0)
    assert alloc.admit(1, 8)
    assert alloc.pages_used() == 1


def test_memory_bound_keeps_shared_prefix_admissible():
    from tpudist.serve import kvcache
    spec = _paged_spec()
    alloc = kvcache.PageAllocator(spec)
    alloc.register_shared(17)                 # 2 full pages reserved
    assert len(alloc.shared_pages) == 2
    # a bound tighter than the registry still keeps its pages usable
    cap = alloc.set_memory_bound(hbm_bytes=1, params_bytes=0,
                                 program_temp_bytes=0)
    assert cap == 2 == len(alloc.shared_pages)
    # shared admissions that fit entirely in registry pages pass the
    # structural check; private pages beyond the cap do not
    assert alloc.can_ever_admit(16, shared=True)
    assert not alloc.can_ever_admit(24, shared=True)


# ----------------------------- state bytes dedupe (the bucket inputs)


def test_state_bytes_per_device_replicated_and_sharded(devices8):
    """The params/opt_state buckets count each leaf ONCE per device:
    replicated leaves in full, sharded leaves by the owned span — on
    both the 1-device and the 4-device mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from tpudist import engine

    x = jnp.arange(1024, dtype=jnp.float32)       # 4096 bytes
    # single device: the whole array lives there
    one = jax.device_put(x, devices8[0])
    assert engine.state_bytes_per_device({"w": one}) == 4096
    mesh = Mesh(devices8[:4], ("d",))
    repl = jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
    shard = jax.device_put(x, NamedSharding(mesh, PartitionSpec("d")))
    # replicated: full bytes per device, NOT 4x (each copy counted on
    # its own device only)
    assert engine.state_bytes_per_device({"w": repl}) == 4096
    # sharded: each device owns a quarter
    assert engine.state_bytes_per_device({"w": shard}) == 1024
    # mixed pytree: max over devices of the summed residency
    assert engine.state_bytes_per_device(
        {"w": repl, "b": shard}) == 4096 + 1024
    assert engine.state_bytes_per_device({}) == 0


def test_train_state_split_feeds_separate_buckets(devices8):
    """init_state's params and opt_state report separately (the two
    ledger buckets) and Adam's two moments make opt_state about twice
    the params footprint."""
    import jax
    from tpudist import engine
    from tpudist.config import DataConfig, ParallelConfig, TrainConfig
    from tpudist.parallel import build_mesh

    cfg = TrainConfig(batch_size=8, data=DataConfig(n_samples=8),
                      parallel=ParallelConfig(data=4))
    mesh = build_mesh(cfg.parallel, devices=devices8[:4])
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    p = engine.state_bytes_per_device(state.params)
    o = engine.state_bytes_per_device(state.opt_state)
    assert p > 0 and o > 0
    assert 1.5 * p <= o <= 3.0 * p, (p, o)


# ------------------------------------------- hbm sampler satellites


def test_hbm_watermark_is_live_buffers_plus_executable_scratch(monkeypatch):
    """The TPU runtime holds a loaded executable's scratch under
    ``bytes_reserved``, outside ``bytes_in_use`` (recorded on a v5e: the
    stats below are that run's, scaled down): the watermark and the
    per-device peaks are the sum, and the reservation is reported as
    itself."""
    import jax
    from tpudist.obs import hbm

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    s = hbm.HbmSampler(period_s=0)
    fields = s.split()
    # the CPU mesh has no device stats: RSS says nothing about the
    # allocator
    if fields["hbm_source"] != "memory_stats":
        assert fields["hbm_bytes_reserved"] is None
        assert fields["hbm_peak_bytes_per_device"] is None
    s = hbm.HbmSampler(period_s=0)
    s.peak_in_use = 0       # forget the construction sample's RSS
    running = {"bytes_in_use": 107, "peak_bytes_in_use": 107,
               "bytes_reserved": 429, "peak_bytes_reserved": 429,
               "bytes_limit": 1690}
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Dev(running), Dev(dict(running))])
    s.sample()
    f = s.split()
    assert f["hbm_source"] == "memory_stats"
    assert f["hbm_peak_bytes"] == 107 + 429
    assert f["hbm_peak_bytes_per_device"] == [536, 536]
    assert f["hbm_bytes_in_use"] == 107 and f["hbm_bytes_reserved"] == 429
    # buffers freed, executable dropped: the watermark does not recede
    idle = {"bytes_in_use": 2, "peak_bytes_in_use": 107,
            "bytes_reserved": 0, "peak_bytes_reserved": 429,
            "bytes_limit": 1690}
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(idle)] * 2)
    s.sample()
    f = s.split()
    assert f["hbm_peak_bytes"] == 536 and f["hbm_bytes_reserved"] == 0
    assert f["hbm_peak_bytes_per_device"] == [536, 536]
    assert f["hbm_peak_fraction"] == round(536 / 1690, 4)
    s.close()


def test_hbm_close_join_is_bounded():
    import time
    from tpudist.obs import hbm
    s = hbm.HbmSampler(period_s=0.05)
    t0 = time.perf_counter()
    s.close()
    assert time.perf_counter() - t0 < 6.0
    assert s.samples >= 2            # construction + the close tail


# --------------------------------------------------- e2e: the train CLI


def _train_cli(tmp_path, capsys, monkeypatch, name, extra=()):
    from tpudist import train as train_mod
    monkeypatch.delenv("TPUDIST_STAGING_BUDGET_MB", raising=False)
    save = tmp_path / name
    rc = train_mod.main(["--epochs", "1", "--train-batch-size", "64",
                         "--n-samples", "640", "--log-every", "0",
                         "--save-dir", str(save)] + list(extra))
    out = capsys.readouterr().out
    assert rc == 0, out
    with open(save / "metrics.jsonl") as f:
        return save, out, [json.loads(ln) for ln in f]


def test_train_cli_emits_exact_memledger(tmp_path, capsys, monkeypatch):
    """THE train acceptance pin: a real CPU-mesh run logs one
    kind=memledger record whose seven buckets sum EXACTLY to the
    pinned device HBM, persists memledger.json, and the forensics CLI
    + report fold it back."""
    monkeypatch.setenv("TPUDIST_HBM_BYTES", str(1 << 30))
    save, out, recs = _train_cli(tmp_path, capsys, monkeypatch, "run")
    leds = [r for r in recs if r.get("kind") == "memledger"]
    assert len(leds) == 1
    rec = leds[0]
    total = rec["total_hbm_bytes"]
    assert total == 1 << 30
    assert sum(rec[f"{k}_bytes"] for k in ml.BUCKETS) == total
    assert rec["params_bytes"] > 0 and rec["opt_state_bytes"] > 0
    assert rec["exact"] is True
    # the CPU watermark is RSS: it must NOT have been reconciled
    assert rec["watermark_source"] == "rss"
    assert rec["residue_bytes"] == 0
    assert rec["hbm_headroom_status"] == "success"
    assert "tpudist: memledger success" in out
    doc = json.load(open(save / ml.LEDGER_NAME))
    assert doc["buckets"]["params"] == rec["params_bytes"]
    assert ml.main(["--run-dir", str(save)]) == 0
    cli_out = capsys.readouterr().out
    assert "partition exact" in cli_out
    assert report_lib.main(["--run-dir", str(save)]) == 0
    rep = json.load(open(save / "run_report.json"))
    assert rep["memory"]["enabled"]
    assert rep["memory"]["buckets"]["params"] == rec["params_bytes"]


def test_train_ledger_informed_budget_is_bitwise_loss_neutral(
        tmp_path, capsys, monkeypatch):
    """Feed-forward acceptance: a prior run's persisted ledger changes
    the auto staging budget (measured scratch margin instead of the
    4x-state guess), the budget changes the slab cuts — and the step
    losses must stay BITWISE identical (the superstep's lo/hi masking
    guarantee)."""
    # the default model holds ~17 KB of state per device and the 640-
    # sample epoch stages ~6.7 KB/device: at 100 KB "HBM" the 4x-state
    # heuristic budget (~16 KB) takes the full-staging fast path while
    # a 75 KB measured-scratch margin streams in slabs
    monkeypatch.setenv("TPUDIST_HBM_BYTES", "100000")
    extra = ["--steps-per-dispatch", "2"]
    _, out_a, ref = _train_cli(tmp_path, capsys, monkeypatch, "cold",
                               extra)
    assert "heuristic 4x-state margin" in out_a
    # seed the save dir with a prior-run ledger carrying a measured
    # (complete) program_temp large enough to move the budget
    save_b = tmp_path / "warm"
    os.makedirs(save_b)
    prior = scripted_ledger(watermark_bytes=None, watermark_source=None)
    prior["buckets"]["program_temp"] = 75000
    prior["program_temp_complete"] = True
    (save_b / ml.LEDGER_NAME).write_text(json.dumps(prior))
    _, out_b, got = _train_cli(tmp_path, capsys, monkeypatch, "warm",
                               extra)
    assert "ledger-informed: prior-run program_temp" in out_b

    def timing(recs):
        return [r for r in recs if r.get("kind") == "timing"][0]

    # the ledger actually moved the budget: full staging became slabs
    assert timing(ref)["staging_streamed"] is False
    assert timing(got)["staging_streamed"] is True

    def losses(recs):
        return [(r["epoch"], r["step"], r["loss"])
                for r in recs if r.get("kind") == "step"]

    assert losses(got) == losses(ref)


# ---------------------------------------- e2e: the paged serve CLI


def test_paged_serve_cli_emits_exact_memledger(tmp_path, capsys,
                                               monkeypatch):
    """THE serve acceptance pin, in process on the CPU mesh: a paged
    serve run logs a kind=memledger record with the KV pool bucket
    equal to PagedCacheSpec.bytes, the partition exact against the
    pinned HBM, the allocator bound logged, and memledger.json folded
    by the report."""
    from tpudist.serve import cli as serve_cli
    monkeypatch.setenv("TPUDIST_HBM_BYTES", str(1 << 30))
    monkeypatch.setenv("TPUDIST_TTFT_P99_MAX", "120")
    monkeypatch.setenv("TPUDIST_ITL_P99_MAX", "60")
    monkeypatch.setenv("TPUDIST_TOKENS_PER_CHIP_MIN", "0.001")
    rc = serve_cli.main(["--requests", "4", "--max-new-tokens", "4",
                         "--request-rate", "200",
                         "--kv-page-tokens", "8",
                         "--save-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "tpudist: serve kv memory bound" in out
    recs = [json.loads(ln) for ln in
            open(tmp_path / "metrics.jsonl")]
    leds = [r for r in recs if r.get("kind") == "memledger"]
    assert len(leds) == 1
    rec = leds[0]
    assert rec["mode"] == "serve"
    assert rec["total_hbm_bytes"] == 1 << 30
    assert sum(rec[f"{k}_bytes"] for k in ml.BUCKETS) \
        == rec["total_hbm_bytes"]
    assert rec["params_bytes"] > 0
    serves = [r for r in recs if r.get("kind") == "serve"]
    assert rec["kv_pool_bytes"] == serves[0]["kv_cache_bytes"] > 0
    assert rec["slabs_bytes"] == 0          # no staging in serve
    doc = json.load(open(tmp_path / ml.LEDGER_NAME))
    assert doc["mode"] == "serve"
    assert any(p.startswith("prefill") for p in doc["programs"])
    assert any(p.startswith("decode") for p in doc["programs"])
    assert report_lib.main(["--run-dir", str(tmp_path)]) == 0
    rep = json.load(open(tmp_path / "run_report.json"))
    assert rep["memory"]["enabled"] and rep["memory"]["mode"] == "serve"
    assert any(p.startswith("decode") for p in rep["memory"]["programs"])
