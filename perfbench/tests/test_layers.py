"""The three readers of the program's layers (``scope_time``,
``scope_unattributed``, ``idle_by_span``) on a small hand-made capture
document and on a trimmed slice of a real v5e capture with scopes and
mirrored spans (``recorded_layers.trace.json.gz``: my chip run, PR 24,
cell internlm2-serve-sat, seed 7101; cut to its first two decode
dispatches; of the arguments the device's ops keep ``tf_op`` and
``run_id``, the nameless converts their ``long_name`` (the HLO text), the
host's events ``long_name`` and ``run_id``)."""

import gzip
import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.lib import capture, manifest
from perfbench.readers import (idle_by_span, layers, scope_time,
                               scope_unattributed)

HERE = os.path.dirname(__file__)
MAN = manifest.load()
NEW = [m["name"] for m in MAN["per_layer"]
       if run_lib.metric_spec(m["name"])["reader"] in (
           "scope_time", "scope_unattributed", "idle_by_span")]


def meta(pid, name, threads):
    out = [{"ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": name}}]
    for tid, tn in threads.items():
        out.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tid, "args": {"name": tn}})
    return out


def op(name, ts, dur, tf_op=None, long_name=None):
    args = {"tf_op": tf_op} if tf_op else {}
    if long_name:
        args["long_name"] = long_name
    return {"ph": "X", "pid": 1, "tid": 1, "name": name, "ts": ts,
            "dur": dur, "args": args}


def note(name, ts, dur):
    # as the session's converter files a TraceAnnotation("tpudist:<name>")
    return {"ph": "X", "pid": 9, "tid": 7, "name": name, "ts": ts,
            "dur": dur, "args": {"long_name": "tpudist:" + name}}


D = "jit(_paged_decode_body)/decode/while/body/closed_call/"
SKEW = 1000.0        # the device line runs this far ahead of the host's


def doc():
    """Two decode dispatches of 400 us with a prefill between them. Host
    times are true times; the device line is stamped SKEW early."""
    ev = meta(1, "/device:TPU:0", {1: "XLA Ops", 2: "XLA Modules"}) \
        + meta(9, "/host:CPU", {7: "python3", 8: "main"})

    def dispatch(t, run_id):
        d = t - SKEW
        ev.extend([
            {"ph": "X", "pid": 1, "tid": 2, "name": "jit__paged_decode_body",
             "ts": d, "dur": 400, "args": {"run_id": run_id}},
            op("while.1", d, 400),
            op("fusion.1", d, 100, D + "attn/kv_write/scatter:"),
            op("fusion.2", d + 100, 100, D + "attn/qkv/dot_general:"),
            op("convert.3", d + 200, 50, D + "attn/qkv/cast/convert:"),
            op("fusion.4", d + 250, 100, D + "ffn/dot_general:"),
            # what the compiler made and moved: no name stack
            op("copy.5", d + 350, 30),
            op("convert.6", d + 380, 20, long_name=(
                "%convert.6 = bf16[24,64,64]{2,1,0} convert(f32[24,64,64]"
                "{2,1,0:T(8,128)} %params__layers____w_up__.1)")),
            {"ph": "X", "pid": 9, "tid": 8, "name": "DoEnqueueProgram",
             "ts": t, "dur": 5, "args": {"run_id": run_id}}])

    # the first dispatch: enqueued at 10000, runs 10000..10400
    ev += [note("profile_window", 9000, 5000), note("admit_pass", 9900, 50),
           note("decode_step", 9950, 500), note("decode_enqueue", 9960, 60),
           note("decode_fence", 10020, 420)]
    dispatch(10000, "1")
    # between the two: emit 40 us, an admit pass holding one prefill whose
    # program runs 10600..10750 (enqueued on a busy queue: no tight pair),
    # then 50 us of a program that has no scope at all
    ev += [note("emit", 10450, 40), note("admit_pass", 10500, 380),
           note("prefill", 10520, 340), note("prefill_enqueue", 10530, 80),
           note("prefill_fence", 10610, 240)]
    ev += [{"ph": "X", "pid": 1, "tid": 2, "name": "jit__paged_prefill_body",
            "ts": 10600 - SKEW, "dur": 150, "args": {"run_id": "2"}},
           op("fusion.9", 10600 - SKEW, 120,
              "jit(_paged_prefill_body)/prefill/closed_call/ffn/dot:"),
           op("fusion.7", 10720 - SKEW, 30),
           {"ph": "X", "pid": 1, "tid": 2, "name": "jit_upload",
            "ts": 10750 - SKEW, "dur": 50, "args": {"run_id": "4"}},
           op("copy.8", 10750 - SKEW, 50),
           {"ph": "X", "pid": 9, "tid": 8, "name": "DoEnqueueProgram",
            "ts": 10560, "dur": 5, "args": {"run_id": "2"}}]
    # the second dispatch: enqueued at 10950, runs 10950..11350
    ev += [note("decode_step", 10900, 500), note("decode_enqueue", 10910, 60),
           note("decode_fence", 10970, 420), note("emit", 11410, 30)]
    dispatch(10950, "3")
    return {"traceEvents": ev}


@pytest.fixture
def view(tmp_path):
    d = tmp_path / "capture"
    d.mkdir()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(doc(), f)
    tracks = capture.load_tracks(str(d))
    return {"kind": "serve", "capture_dir": str(d), "tracks": tracks,
            "capture": capture.reduce_tracks(tracks), "spans": [],
            "job": {"capture_dispatches": 2}}


def test_the_device_line_is_put_on_the_hosts_clock_by_run_id(view):
    lay = layers.parse(view)
    assert lay["skew_us"] == pytest.approx(SKEW)
    assert lay["per"] == {"step": 0, "dispatch": 2, "prefill": 1}
    dev = lay["devices"][0]
    # window: first op of the stretch to the end of the second fence
    assert dev["window"] == pytest.approx((10000 - SKEW, 11390 - SKEW))
    assert capture.measure(dev["busy"]) == pytest.approx(1000)
    assert capture.measure(dev["idle"]) == pytest.approx(390)
    assert "layers" in view and layers.parse(view) is lay   # read once


def test_scope_time_sums_by_path_and_leaves_containers_out(view):
    def ms(scope, **kw):
        return scope_time.read(view, {"scope": scope, **kw}, {})
    dec = "^decode/(.*/)?"
    assert ms(dec + "attn/(kv_write|kv_gather)(/|$)",
              per="dispatch") == pytest.approx(0.100)
    assert ms(dec + "attn/(qkv|rope|core|out)(/|$)",
              exclude="(^|/)cast(/|$)", per="dispatch") \
        == pytest.approx(0.100)
    assert ms(dec + "ffn(/|$)", per="dispatch") == pytest.approx(0.100)
    # per program instance: the one prefill's 150 us, not 150 / count;
    # a program the capture never shows reads 0, not the stretch's sum
    assert ms("", module="prefill", per="prefill") == pytest.approx(0.150)
    assert ms("", module="no_such_program", per="prefill") == 0.0
    assert ms("^nothing$", per="dispatch") == 0.0


def test_nameless_ops_are_booked_by_program(view):
    def ms(scope, per="dispatch"):
        return scope_time.read(view, {"scope": scope, "per": per}, {})
    # the decode program's copy goes to its outermost scope alone, the
    # hoisted convert of a stored weight to its cast (beside the 50 us
    # of the cast that kept its name stack)
    assert ms("^decode$") == pytest.approx(0.030)
    assert ms("^decode/(.*/)?cast(/|$)") == pytest.approx(0.050 + 0.020)
    assert ms("^prefill$", "prefill") == pytest.approx(0.030)
    paths = {p for _, _, p in layers.parse(view)["devices"][0]["leaves"]}
    assert "decode/cast" in paths and "" in paths
    # a step with several outermost scopes keeps its nameless ops bare
    lv = [(0, 1, "loss/ffn"), (1, 2, ""), (2, 3, "optimizer")]
    layers.book_by_program(lv, [(0, 3, "jit_step", "1")], {(1, 2)})
    assert lv[1] == (1, 2, "")


def test_the_sums_close_on_busy_time(view):
    lay = layers.parse(view)
    busy = capture.measure(lay["devices"][0]["busy"])
    named = sum(scope_time.read(view, {"scope": s}, {}) for s in (
        "attn/kv_write", "attn/qkv$", "cast", "^decode/ffn", "^prefill",
        "^decode$"))
    bare = scope_unattributed.read(view, {}, {})
    assert bare == pytest.approx(100 * 50 / 1000)   # the scopeless program
    assert named * 1e3 + bare / 100 * busy == pytest.approx(busy)
    # and against the harness's own reduction of the same tracks
    assert busy / 1e6 == pytest.approx(view["capture"]["busy_s"])


def test_innermost_span_wins_and_the_bracket_never_does(view):
    lay = layers.parse(view)
    spans = [s for s in lay["spans"] if s[2] not in layers.SKIP]
    by = layers.innermost(lay["devices"][0]["idle"], spans)
    # idle 10400..10600: decode_fence until 10440, its decode_step until
    # 10450, emit until 10490, nothing until 10500, admit_pass until
    # 10520, prefill until 10530, prefill_enqueue until 10600; idle
    # 10800..10950: prefill_fence until 10850, prefill until 10860,
    # admit_pass until 10880, nothing until 10900, decode_step until
    # 10910, decode_enqueue until 10950; idle 11350..11390: decode_fence
    assert by == pytest.approx({
        "decode_fence": 80, "decode_step": 20, "emit": 40, "": 30,
        "admit_pass": 40, "prefill": 20, "prefill_enqueue": 70,
        "prefill_fence": 50, "decode_enqueue": 40})
    assert "profile_window" not in by
    assert sum(by.values()) == pytest.approx(
        capture.measure(lay["devices"][0]["idle"]))

    def ms(names, per="dispatch"):
        return idle_by_span.read(view, {"names": names, "per": per}, {})
    assert ms(["decode_enqueue"]) == pytest.approx(0.020)
    assert ms(["decode_fence"]) == pytest.approx(0.040)
    assert ms(["emit", "tick", "idle_wait"]) == pytest.approx(0.020)
    assert ms(["idle_wait"]) == 0.0
    assert ms(["admit_pass", "admit", "prefill", "prefill_enqueue",
               "prefill_fence"]) == pytest.approx(0.090)
    assert ms(["prefill_enqueue", "prefill_fence"], "prefill") \
        == pytest.approx(0.120)
    assert idle_by_span.read(view, {"unattributed": True}, {}) \
        == pytest.approx(100 * 30 / 390)


def test_without_the_skew_the_gaps_would_go_to_the_wrong_spans(view):
    lay = layers.parse(view)
    late = [(a + SKEW, b + SKEW, n) for a, b, n in lay["spans"]
            if n not in layers.SKIP]
    by = layers.innermost(lay["devices"][0]["idle"], late)
    # the document's two lines read as they stand: seven eighths of the
    # idle time under no span, and no prefill seen in any gap
    assert by == pytest.approx({"": 350, "decode_fence": 40})


def test_a_program_without_scopes_or_spans_reads_as_unattributed(view,
                                                                 tmp_path):
    """The parent commit under this PR's benchmark files: no ``tf_op``
    names a scope, no annotation is mirrored. Every reader still gives a
    number: nothing is booked, everything is unattributed."""
    bare = doc()
    bare["traceEvents"] = [
        {**e, "args": {k: v for k, v in e.get("args", {}).items()
                       if k != "tf_op"}} if e.get("pid") == 1 else e
        for e in bare["traceEvents"]
        if not str(e.get("args", {}).get("long_name", "")).startswith(
            "tpudist:")]
    d = tmp_path / "bare"
    d.mkdir()
    with open(d / "host.trace.json", "w") as f:
        json.dump(bare, f)
    tracks = capture.load_tracks(str(d))
    v = {"kind": "serve", "capture_dir": str(d), "tracks": tracks,
         "capture": capture.reduce_tracks(tracks),
         "job": {"capture_dispatches": 2},
         "capture_stretch_us": (50.0, 2000.0),
         "spans": [{"name": "prefill", "t0_us": 500.0, "t1_us": 900.0}]}
    lay = layers.parse(v)
    assert lay["per"] == {"step": 0, "dispatch": 2, "prefill": 1}
    for name in NEW:
        spec = run_lib.metric_spec(name)
        got = run_lib.read_metric(name, v, {})
        assert got is not None, name
        if spec["reader"] == "scope_unattributed" \
                or spec["params"].get("unattributed"):
            assert got == pytest.approx(100.0), name
        elif spec["params"].get("module"):
            # read off the program's name on the modules line, which the
            # parent has too: the one prefill's 150 us
            assert got == pytest.approx(0.150), name
        else:
            assert got == 0.0, name


# ---------------------------------------------- the recorded v5e slice


@pytest.fixture
def recorded(tmp_path):
    import shutil
    d = tmp_path / "capture"
    d.mkdir()
    shutil.copy(os.path.join(HERE, "recorded_layers.trace.json.gz"), d)
    tracks = capture.load_tracks(str(d))
    return {"kind": "serve", "capture_dir": str(d), "tracks": tracks,
            "capture": capture.reduce_tracks(tracks), "spans": [],
            "job": {"capture_dispatches": 2}}


def test_recorded_slice_sums_close(recorded):
    lay = layers.parse(recorded)
    assert lay["per"] == {"step": 0, "dispatch": 2, "prefill": 3}
    assert lay["skew_us"] == pytest.approx(1734.290)
    dev = lay["devices"][0]
    busy, idle = capture.measure(dev["busy"]), capture.measure(dev["idle"])
    # leaves only, and still the harness's own busy time of these tracks
    assert busy / 1e6 == pytest.approx(recorded["capture"]["busy_s"])
    assert busy + idle == pytest.approx(dev["window"][1] - dev["window"][0])
    # (stamps rounded to the nanosecond: 31,000 leaves overlap by 1.6 us)
    assert sum(b - a for a, b, _ in dev["leaves"]) \
        == pytest.approx(busy, abs=5.0)
    spans = [s for s in lay["spans"] if s[2] not in layers.SKIP]
    by = layers.innermost(dev["idle"], spans)
    assert sum(by.values()) == pytest.approx(idle)
    # every named millisecond and the unnamed share make up the busy time
    named = sum(b - a for a, b, p in dev["leaves"] if p)
    bare = scope_unattributed.read(recorded, {}, {})
    assert named + bare / 100 * busy == pytest.approx(busy, abs=5.0)


def test_recorded_slice_innermost_wins(recorded):
    lay = layers.parse(recorded)
    dev = lay["devices"][0]
    spans = [s for s in lay["spans"] if s[2] not in layers.SKIP]
    by = layers.innermost(dev["idle"], spans)
    # three prefills sit in one admit_pass between the two dispatches: the
    # device waits 8.7 ms while the host ENQUEUES them and 2.9 ms at
    # their fences; the pass that covers all of it keeps 0.4 ms
    assert by["prefill_enqueue"] / 1e3 == pytest.approx(8.688, abs=2e-3)
    assert by["prefill_fence"] / 1e3 == pytest.approx(2.936, abs=2e-3)
    assert by["admit_pass"] / 1e3 == pytest.approx(0.429, abs=2e-3)
    assert by[""] / 1e3 == pytest.approx(0.122, abs=2e-3)
    # read without the measured offset, the enqueues' gaps move under the
    # fences that follow them
    late = [(a + lay["skew_us"], b + lay["skew_us"], n)
            for a, b, n in spans]
    off = layers.innermost(dev["idle"], late)
    assert off["prefill_enqueue"] < 0.6 * by["prefill_enqueue"]


@pytest.mark.parametrize("name,value", [
    ("decode_scope_ms.kv.sat", 1.918729),
    ("decode_scope_ms.attn.sat", 17.520631),
    ("decode_scope_ms.ffn.sat", 27.709012),
    # 5.70 ms of casts kept their name stack; 13.58 ms are the hoisted
    # weight converts, nameless, told by their HLO text
    ("decode_scope_ms.cast.sat", 19.284812),
    # 58.5 ms of the scans' own slicing and restacking, named ``decode``
    # and nothing else, and 38.6 ms of nameless compiler-made copies
    ("decode_scope_ms.loop.sat", 97.073247),
    ("scope_unattributed_pct.serve_sat", 0.0),
    ("idle_ms_per_dispatch.enqueue.sat", 0.462905),
    ("idle_ms_per_dispatch.fence.sat", 1.712665),
    ("idle_ms_per_dispatch.emit.sat", 0.298040),
    ("idle_ms_per_dispatch.admit.sat", 6.052971),
    ("idle_unattributed_pct.serve_sat", 0.708699),
    ("prefill_device_ms_p50.open", 37.629987),
    ("idle_ms_per_prefill.open", 3.874681),
    ("idle_unattributed_pct.serve_open", 0.708699),
    ("idle_ms_per_dispatch.wait.open", 0.0),
])
def test_recorded_slice_reads_each_serve_metric(recorded, name, value):
    assert run_lib.read_metric(name, recorded, {}) \
        == pytest.approx(value, rel=1e-5, abs=1e-9)


def test_no_capture_means_no_number():
    assert scope_time.read({"capture_dir": None}, {"scope": "x"}, {}) is None
    assert idle_by_span.read({}, {"unattributed": True}, {}) is None
    assert scope_unattributed.read({"tracks": {}}, {}, {}) is None


def test_words_are_the_programs_scopes():
    from tpudist import scopes
    assert layers.WORDS == {w for s in scopes.SCOPES for w in s.split("/")}
    for name in ("jit(f)/transpose(jvp(loss))/attn/qkv/dot_general:",
                 "jit(f)/decode/while/body/closed_call/cast/convert:",
                 "jit(f)/jit(norm)/mul:", "", None):
        assert layers.scope_path(name) == scopes.scope_path(name)


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_lower_better_device_trace_with_a_cell(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert entry["better"] == "lower" and entry["source"] == "device_trace"
    assert len(entry["workloads"]) == 1
    e2e = manifest.end_to_end(MAN, entry["workloads"][0])
    assert entry["moves"] in e2e
    spec = run_lib.metric_spec(name)
    assert set(spec) == {"reader", "params"}
