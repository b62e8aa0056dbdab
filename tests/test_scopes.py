"""The program's layers in a capture (PR 24): the scope names as an
interface (``tpudist.scopes``), executables that are guaranteed to carry
them (the cache key), the ring tracer's mirror into the profiler, the
serve loop's span tree, the checkpoint's host counters, and ``by_scope``
in the devtime record and the run report."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import checkpoint, engine, scopes
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig)
from tpudist.elastic import ckpt as eck
from tpudist.obs import devtime
from tpudist.obs import report as report_lib
from tpudist.obs import trace as trace_mod
from tpudist.parallel import build_mesh
from tpudist.serve import flight as flight_lib
from tpudist.serve import scheduler as sched
from tpudist.serve.engine import PagedServeEngine, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)


# ------------------------------------------------- scopes in the programs


@pytest.fixture(scope="module")
def lowered_names():
    """The name stacks of the tiny train step, the paged prefill and the
    paged decode, as lowered on the CPU: {program: set of loc names}."""
    dev = jax.devices()[:1]
    mesh = build_mesh(ParallelConfig(), devices=dev)
    cfg = TrainConfig(batch_size=2, dtype="bfloat16",
                      data=DataConfig(n_samples=4),
                      model=TINY_TF)
    step = engine.make_train_step(cfg, mesh)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    batch = np.zeros((2, 17), np.int32)
    state, _ = step(state, batch)

    def names(text):
        return set(re.findall(r'loc\("([^"]+)"', text))

    out = {"train": names(step.lowered_text(debug_info=True))}
    eng = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                           prompt_pad=4, decode_k=2, page_tokens=4,
                           dtype=jnp.bfloat16)
    eng.warmup(init_params(TINY_TF, mesh, seed=0))
    for prog in ("prefill", "decode_k2"):
        jitted, args = eng._programs[prog]
        out[prog] = names(jitted.lower(*args).as_text(debug_info=True))
    return out


@pytest.mark.parametrize("name", scopes.SCOPES)
def test_every_scope_is_in_a_lowered_program(lowered_names, name):
    where = {"loss": ("train",), "optimizer": ("train",),
             "prefill": ("prefill",), "kv_scatter": ("prefill",),
             "decode": ("decode_k2",), "sample": ("prefill", "decode_k2")
             }.get(name, ("train", "prefill", "decode_k2"))
    hits = [p for p in where if any(
        re.search(rf"(^|[/(]){re.escape(name)}([/)]|$)", n)
        for n in lowered_names[p])]
    assert hits, f"scope {name!r} is in none of {where}"


def test_backward_ops_carry_the_forward_names(lowered_names):
    # (a scanned layer body is a function of its own in the lowered text,
    # its names relative to the call; the compiler joins them)
    paths = {scopes.scope_path(n) for n in lowered_names["train"]}
    assert {"attn/qkv", "ffn", "ffn/cast", "loss/lm_head", "optimizer",
            "loss/embed", "loss/cast"} <= paths
    assert any("transpose(jvp(loss))/lm_head/dot_general" in n
               for n in lowered_names["train"])


def test_serve_paths_sit_under_their_program(lowered_names):
    pre = {scopes.scope_path(n) for n in lowered_names["prefill"]}
    dec = {scopes.scope_path(n) for n in lowered_names["decode_k2"]}
    assert {"prefill/kv_scatter", "prefill/attn/kv_write", "attn/rope",
            "prefill/lm_head", "prefill/embed"} <= pre
    assert any(n.endswith("/prefill/sample")
               for n in lowered_names["prefill"])
    assert {"decode", "attn/kv_write", "attn/kv_gather", "attn/core",
            "attn/qkv", "ffn", "norm"} <= dec
    # the bfloat16 engine was handed float32 weights and holds them at
    # rest in bfloat16: neither program converts one
    assert not any("cast" in p for p in pre | dec)
    assert not any("prefill" in p for p in dec)
    assert not any("decode" in p for p in pre)


@pytest.mark.parametrize("op_name,path,layer", [
    ("jit(superstep)/while/body/closed_call/transpose(jvp(loss))/attn/qkv/"
     "dot_general:", "loss/attn/qkv", "attn"),
    ("jit(f)/jvp(loss)/attn/core/flash_fwd/pallas_call:", "loss/attn/core",
     "attn"),
    ("jit(f)/decode/while/body/closed_call/cond/branch_1_fun/attn/kv_write/"
     "scatter:", "decode/attn/kv_write", "attn"),
    ("jit(f)/jvp(loss)/ffn/cast/convert_element_type:", "loss/ffn/cast",
     "cast"),
    ("jit(f)/decode/while/body/add:", "decode", "decode"),
    ("jit(f)/jit(norm)/mul:", "", ""),       # a jitted helper, not a scope
    ("jit(f)/optimizer/mul", "optimizer", "optimizer"),
    ("", "", ""), (None, "", ""),
])
def test_scope_path_and_layer(op_name, path, layer):
    assert scopes.scope_path(op_name) == path
    assert scopes.layer_of(path) == layer


def test_scope_refuses_a_name_outside_the_interface():
    with pytest.raises(ValueError):
        scopes.scope("attention")


# ------------------------------------------------------- the cache key


_CACHE_PROBE = """
import os, sys
sys.path.insert(0, {repo!r})
from tpudist.utils.platform import enable_compilation_cache, CACHE_EVENTS
enable_compilation_cache()
import jax, jax.numpy as jnp
def f(x, w):
    {pad}
    if {scoped}:
        with jax.named_scope("attn/qkv"):
            return jnp.tanh(x @ w)
    return jnp.tanh(x @ w)
x = jnp.ones((64, 64))
jax.block_until_ready(x)
before = dict(CACHE_EVENTS)
exe = jax.jit(f).lower(x, x).compile()
hits = CACHE_EVENTS["/jax/compilation_cache/cache_hits"] \\
    - before["/jax/compilation_cache/cache_hits"]
print("RESULT", int("attn/qkv" in exe.as_text()), hits)
"""


def _cache_probe(tmp_path, scoped, pad="pass"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(
            repo=REPO, scoped=scoped, pad=pad)],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    has, hits = p.stdout.rsplit("RESULT", 1)[1].split()
    return bool(int(has)), int(hits)


def test_a_scoped_program_is_never_served_an_unscoped_executable(tmp_path):
    """Two processes, one cache directory: the same function without and
    then with a scope. With jax's default key (debug info stripped) the
    second loads the first's executable, scope-less."""
    assert _cache_probe(tmp_path, False) == (False, 0)
    has, hits = _cache_probe(tmp_path, True)
    assert has and hits == 0
    # the same scoped program again, its lines moved: a hit, scoped
    has, hits = _cache_probe(tmp_path, True, pad="pass\n    pass\n    pass")
    assert has and hits == 1


# ------------------------------------------------ the profiler's mirror


class FakeAnnotation:
    log = []

    def __init__(self, name, **kw):
        assert not kw
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def fake_mirror(monkeypatch):
    FakeAnnotation.log = []
    monkeypatch.setattr(trace_mod, "_annotation_cls",
                        lambda: FakeAnnotation)
    return FakeAnnotation.log


def test_mirror_enters_and_leaves_one_annotation_per_span(fake_mirror):
    tr = trace_mod.Tracer(capacity=16)
    with tr.span("outer", cat="t", x=1):
        with tr.span("inner", cat="t"):
            pass
    h = tr.begin("be")
    tr.end(h)
    tr.instant("mark")                  # zero length: not mirrored
    assert fake_mirror == [
        ("enter", "tpudist:outer"), ("enter", "tpudist:inner"),
        ("exit", "tpudist:inner"), ("exit", "tpudist:outer"),
        ("enter", "tpudist:be"), ("exit", "tpudist:be")]
    assert [e["name"] for e in tr.events()] == [
        "outer", "inner", "be", "mark"]


def test_mirror_is_the_real_trace_annotation_or_nothing_without_jax(
        monkeypatch):
    assert trace_mod._annotation_cls() is jax.profiler.TraceAnnotation
    with trace_mod.Tracer(capacity=4).span("real"):
        pass                             # no session open: records nothing
    monkeypatch.setitem(sys.modules, "jax.profiler", None)   # jax-free host
    assert trace_mod._annotation_cls() is None


def test_disabled_tracer_reads_no_clock_and_resolves_no_mirror(
        monkeypatch):
    reads = []
    real = trace_mod._now_ns
    monkeypatch.setattr(trace_mod, "_now_ns",
                        lambda: reads.append(1) or real())
    monkeypatch.setattr(trace_mod, "_annotation_cls", lambda: 1 / 0)
    tr = trace_mod.Tracer(enabled=False)
    n0 = len(reads)
    with tr.span("a") as sp:
        pass
    tr.end(tr.begin("b"))
    trace_mod.HostCost(sp).note(bytes=1)
    assert len(reads) == n0 and tr.span_count == 0


def test_tracer_module_imports_no_jax_until_a_span_is_taken():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from tpudist.obs import trace\n"
            "t = trace.Tracer(enabled=False)\n"
            "with t.span('x'): pass\n"
            "assert 'jax' not in sys.modules, 'disabled tracer'\n"
            "with trace.Tracer().span('y'): pass\n"
            "assert 'jax' in sys.modules, 'enabled tracer mirrors'\n"
            % REPO)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]


def test_span_note_merges_into_recorded_args():
    tr = trace_mod.Tracer(capacity=4)
    with tr.span("s", cat="t", a=1) as sp:
        sp.note(b=2)
    assert tr.events()[0]["args"] == {"a": 1, "b": 2}


def test_window_profiler_opens_without_the_python_tracer(tmp_path,
                                                         monkeypatch):
    seen = {}

    def start_trace(log_dir, **kw):
        seen["dir"], seen["opts"] = log_dir, kw.get("profiler_options")

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tr = trace_mod.configure(enabled=True)
    try:
        win = devtime.WindowProfiler(str(tmp_path), 1)
        win.maybe_start(0)
        t_after = trace_mod._now_ns()
        win.note_dispatch()
    finally:
        trace_mod.configure()
    assert seen["opts"].python_tracer_level == 0
    assert seen["opts"].host_tracer_level == 1
    # the bracket span and the anchor stay where they were: the span
    # opens before the anchor is read, the anchor before the session
    span = next(e for e in tr.events() if e["name"] == "profile_window")
    assert span["ts"] * 1e3 <= win.anchor_ns <= t_after


# ------------------------------------------------- the serve loop's spans


class RecMetrics:
    def __init__(self):
        self.recs = []

    def log(self, **kv):
        self.recs.append(kv)

    def flush(self):
        pass


@pytest.fixture(scope="module")
def serve_run():
    tracer = trace_mod.configure(enabled=True)
    try:
        mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
        params = init_params(TINY_TF, mesh, seed=0)
        eng = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                               prompt_pad=4, decode_k=4, page_tokens=4)
        eng.warmup(params)
        reqs = sched.make_requests(6, prompt_pad=4, vocab_size=64,
                                   max_new=6, rate=400.0, seed=3)
        m = RecMetrics()
        summary = sched.run_serve(eng, params, reqs, metrics=m,
                                  tick_every=2)
        events = tracer.events(process_index=0)
        doc = {"metadata": {"dropped": tracer.dropped},
               "traceEvents": events}
    finally:
        trace_mod.configure()
    return summary, m.recs, events, doc


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and child["ts"] + child["dur"] \
        <= parent["ts"] + parent["dur"] + 1e-6


def _named(events, name):
    return [e for e in events if e["name"] == name and e["ph"] == "X"]


@pytest.mark.parametrize("child,parent", [
    ("prefill", "admit_pass"), ("admit", "admit_pass"),
    ("prefill_enqueue", "prefill"), ("prefill_fence", "prefill"),
    ("decode_enqueue", "decode_step"), ("decode_fence", "decode_step"),
])
def test_serve_span_tree_nests_as_specified(serve_run, child, parent):
    _, _, events, _ = serve_run
    kids, folks = _named(events, child), _named(events, parent)
    assert kids and folks
    for k in kids:
        assert sum(_inside(k, p) for p in folks) == 1, (child, k)


def test_serve_span_tree_counts_and_order(serve_run):
    summary, _, events, _ = serve_run
    n = summary["dispatches"]
    assert n >= 2
    for name in ("decode_step", "decode_enqueue", "decode_fence", "emit"):
        assert len(_named(events, name)) == n, name
    assert len(_named(events, "prefill")) \
        == len(_named(events, "prefill_enqueue")) \
        == len(_named(events, "prefill_fence")) == summary["admitted"] == 6
    assert len(_named(events, "tick")) == n // 2
    assert all(e["cat"] == "serve" for name in (
        "admit_pass", "prefill_enqueue", "prefill_fence",
        "decode_enqueue", "decode_fence", "emit", "tick")
        for e in _named(events, name))
    # one loop pass: admit_pass, then decode_step (enqueue before fence),
    # then emit, and nothing of one inside another
    steps, emits = _named(events, "decode_step"), _named(events, "emit")
    passes = _named(events, "admit_pass")
    for st, em in zip(steps, emits):
        enq = next(e for e in _named(events, "decode_enqueue")
                   if _inside(e, st))
        fen = next(e for e in _named(events, "decode_fence")
                   if _inside(e, st))
        assert enq["ts"] + enq["dur"] <= fen["ts"] + 1e-6
        assert st["ts"] + st["dur"] <= em["ts"] + 1e-6
        assert any(p["ts"] + p["dur"] <= st["ts"] + 1e-6 for p in passes)
    # decode_step keeps the args its two metrics read; the new spans
    # carry none (nothing reads any)
    assert {"active", "decode_k"} <= set(steps[0]["args"])
    assert not any(e.get("args") for e in passes + emits)


def test_flight_ledger_still_verifies_with_the_new_spans(serve_run):
    summary, recs, _, doc = serve_run
    res = flight_lib.verify(flight_lib.reconstruct(recs, doc),
                            summary["partition"])
    assert res["exact"], res["problems"]
    assert res["trace_checked"] == summary["admitted"]


def test_idle_wait_span_covers_the_sleep_between_arrivals():
    tracer = trace_mod.configure(enabled=True)
    try:
        mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
        params = init_params(TINY_TF, mesh, seed=0)
        eng = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                               prompt_pad=4, decode_k=4, page_tokens=4)
        eng.warmup(params)
        reqs = sched.make_requests(2, prompt_pad=4, vocab_size=64,
                                   max_new=2, rate=20.0, seed=5)
        sched.run_serve(eng, params, reqs)
        waits = _named(tracer.events(process_index=0), "idle_wait")
    finally:
        trace_mod.configure()
    assert waits and all(e["cat"] == "serve" for e in waits)


# ------------------------------------------------ checkpoint counters


def _enqueue_args(tracer):
    spans = [e for e in tracer.events() if e["name"] == "ckpt_enqueue"]
    assert len(spans) == 1
    return spans[0]["args"]


@pytest.mark.parametrize("mode", ["orbax", "sharded"])
def test_ckpt_enqueue_carries_bytes_and_cpu_seconds(tmp_path, mode):
    cfg = TrainConfig(batch_size=32, data=DataConfig(n_samples=64))
    mesh = build_mesh(cfg.parallel, devices=jax.devices()[:1])
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    want = sum(int(x.nbytes) for x in jax.tree.leaves(state))
    tracer = trace_mod.configure(enabled=True)
    try:
        if mode == "orbax":
            ck = checkpoint.Checkpointer(str(tmp_path))
            ck.save(state, epoch=1)
            ck.wait()
            ck.close()
        else:
            ck = eck.ShardedCheckpointer(str(tmp_path), use_async=False)
            ck.save(state, epoch=1, step_in_epoch=0)
            ck.close()
        args = _enqueue_args(tracer)
    finally:
        trace_mod.configure()
    assert args["bytes"] == want > 0
    assert isinstance(args["cpu_s"], float) and args["cpu_s"] >= 0
    assert "nivcsw" not in args       # reads 0 on the chip's host: left out
    assert "step" in args


def test_run_report_reads_the_enqueue_counters():
    """``bytes`` and ``cpu_s`` are the operator's: the run report says
    how many cores the snapshot kept busy."""
    ev = {"ph": "X", "pid": 0, "tid": 1, "cat": "ckpt", "ts": 0.0,
          "name": "ckpt_enqueue", "dur": 4e6,
          "args": {"step": 8, "bytes": 5_704_458_248, "cpu_s": 28.0}}
    rep = report_lib.build_report([], {"traceEvents": [ev, dict(ev)]})
    assert rep["ckpt"]["enqueue_bytes"] == 2 * 5_704_458_248
    assert rep["ckpt"]["enqueue_cpu_s"] == pytest.approx(56.0)
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "enqueue snapshots" in ln)
    assert "11.409 GB" in line and "56.00 CPU-s (7.0 cores busy)" in line
    # spans of a run before PR 24 carry neither: the line is left out
    del ev["args"]
    old = report_lib.build_report([], {"traceEvents": [ev]})
    assert "enqueue snapshots" not in report_lib.to_markdown(old)


# ------------------------------------- by_scope: record and run report


def _capture_doc():
    def op(name, ts, dur, tf_op=None):
        args = {"tf_op": tf_op} if tf_op else {}
        return {"ph": "X", "pid": 1, "tid": 1, "name": name, "ts": ts,
                "dur": dur, "args": args}
    j = "jit(superstep)/while/body/closed_call/"
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Modules"}},
        op("while.3", 0, 1000),                      # a container
        op("fusion.1", 0, 300, j + "jvp(loss)/ffn/dot_general:"),
        op("fusion.2", 300, 200,
           j + "transpose(jvp(loss))/attn/qkv/dot_general:"),
        op("convert.4", 500, 100, j + "jvp(loss)/ffn/cast/convert:"),
        op("copy.9", 600, 50),                       # compiler-made
        op("fusion.7", 700, 300, j + "optimizer/mul:"),
        {"ph": "X", "pid": 1, "tid": 2, "name": "jit_superstep", "ts": 0,
         "dur": 1000},
    ]}


def test_scope_seconds_books_leaves_by_layer():
    got = devtime.scope_seconds(_capture_doc())
    assert got == pytest.approx({"ffn": 300e-6, "attn": 200e-6,
                                 "cast": 100e-6, "": 50e-6,
                                 "optimizer": 300e-6})


def test_leaf_without_a_path_takes_its_containers():
    evs = [(0.0, 100.0, "decode"), (10.0, 20.0, ""),
           (30.0, 40.0, "decode/ffn"), (200.0, 210.0, "")]
    assert devtime.leaf_events(evs) == [
        (10.0, 20.0, "decode"), (30.0, 40.0, "decode/ffn"),
        (200.0, 210.0, "")]


def test_analyze_capture_returns_by_scope_largest_first(tmp_path):
    with open(tmp_path / "h.trace.json", "w") as f:
        json.dump(_capture_doc(), f)
    out = devtime.analyze_capture(str(tmp_path))
    assert list(out["by_scope"]) == ["ffn", "optimizer", "attn", "cast", ""]
    # the leaves only: the 1000 us ``while`` around them is not work
    assert sum(out["by_scope"].values()) == pytest.approx(950e-6)


def test_run_report_prints_device_time_by_scope():
    rec = {"kind": "devtime", "comm_status": "success", "devices": 1,
           "window_s": 0.001, "compute_s": 0.00095, "comm_s": 0.0,
           "exposed_comm_s": 0.0, "exposed_comm_frac": 0.0,
           "per_device": [{"device": "TPU:0", "compute_s": 0.00095,
                           "comm_s": 0.0, "exposed_comm_s": 0.0,
                           "idle_frac": 0.05}],
           "by_scope": {"ffn": 0.0003, "optimizer": 0.0003, "attn": 0.0002,
                        "cast": 0.0001, "": 0.00005}}
    rep = report_lib.build_report([rec], {"traceEvents": []})
    assert rep["devtime"]["by_scope"] == rec["by_scope"]
    line = next(ln for ln in report_lib.to_markdown(rep).splitlines()
                if "device time by program scope" in ln)
    assert "ffn 0.000s (31.6%)" in line and "(no scope)" in line
