"""Set-up traced where it happens: jax's compile phases as ring-tracer
spans (``utils.platform``'s one listener), the serve engine's own set-up
spans, and the benchmark's reader that splits ``setup_s`` by them
(``perfbench/readers/setup_by_span.py``)."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import manifest
from perfbench.readers import layers, setup_by_span
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.obs import trace as trace_mod
from tpudist.parallel import build_mesh
from tpudist.serve.engine import PagedServeEngine, init_params
from tpudist.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("compile_trace", "compile_lower", "compile_backend")
TRACE, LOWER, BACKEND = platform.COMPILE_SPANS
TRACE_SPAN, LOWER_SPAN, _ = PHASES

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)
TINY_SDAR = ModelConfig(
    name="sdarmoe", vocab_size=64, n_layers=1, d_model=32, n_heads=4,
    n_kv_heads=2, head_dim=8, d_ff=16, n_experts=4, expert_top_k=2,
    rope_theta=1e6, norm_eps=1e-6, block_length=4, denoise_steps=4,
    mask_token_id=63)


@pytest.fixture
def tracer():
    platform.listen_to_compiles()
    tr = trace_mod.configure(enabled=True)
    yield tr
    trace_mod.configure()


def _named(tr, *names):
    return [e for e in tr.events() if e["name"] in names]


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and child["ts"] + child["dur"] \
        <= parent["ts"] + parent["dur"] + 1e-3


# ------------------------------------------------------- the listener


def test_a_first_call_records_its_three_phases_inside_the_open_span(
        tracer):
    def setup_probe(x):
        return jnp.tanh(x @ x) * 3.0
    f = jax.jit(setup_probe)
    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    with tracer.span("outer", cat="t"):
        jax.block_until_ready(f(x))
    outer = _named(tracer, "outer")[0]
    mine = [e for e in _named(tracer, *PHASES)
            if "setup_probe" in e["args"]["fun"]]
    # one registration however often it is asked for: one span a phase
    platform.listen_to_compiles()
    assert sorted(e["name"] for e in mine) == sorted(PHASES)
    for e in mine:
        assert _inside(e, outer), e
        assert e["cat"] == "compile" and e["dur"] >= 0
    n = len(tracer.events())
    jax.block_until_ready(f(x))
    assert len(tracer.events()) == n      # a second call compiles nothing


def test_the_phases_follow_jax_own_events():
    assert set(platform.COMPILE_SPANS.values()) == set(PHASES)
    from jax._src import dispatch
    assert {dispatch.JAXPR_TRACE_EVENT, dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
            dispatch.BACKEND_COMPILE_EVENT} == set(platform.COMPILE_SPANS)


_CACHE_PROBE = """
import sys
sys.path.insert(0, {repo!r})
from tpudist.utils.platform import enable_compilation_cache
enable_compilation_cache()
from tpudist.obs import trace
import jax, jax.numpy as jnp
tr = trace.configure(enabled=True)
def cached_probe(x, w):
    return jnp.tanh(x @ w)
x = jnp.ones((64, 64))
jax.block_until_ready(x)
jax.jit(cached_probe).lower(x, x).compile()
print("RESULT", [e["args"].get("cache") for e in tr.events()
                 if e["name"] == "compile_backend"
                 and "cached_probe" in e["args"]["fun"]])
"""


def test_a_second_process_on_the_cache_marks_the_backend_compile_a_hit(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    seen = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)],
            env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        seen.append(p.stdout.rsplit("RESULT", 1)[1].strip())
    assert seen == ["['miss']", "['hit']"]


def test_a_disabled_tracer_costs_the_listener_no_clock_read(monkeypatch):
    platform.listen_to_compiles()
    reads = []
    real = trace_mod._now_ns
    monkeypatch.setattr(trace_mod, "_now_ns",
                        lambda: reads.append(1) or real())
    tr = trace_mod.configure(enabled=False)
    n0 = len(reads)             # the tracer's own start stamp
    try:
        platform._record_compile(BACKEND, 0.25, fun_name="f")
        jax.block_until_ready(jax.jit(lambda x: x * 7.0 + 1.0)(
            jnp.ones((3,))))
        n1 = len(reads)
    finally:
        trace_mod.configure()
    assert n1 == n0 and tr.span_count == 0


@pytest.mark.parametrize("args,kw", [
    ((BACKEND,), {}),
    ((BACKEND, None), {"fun_name": "f"}),
    ((BACKEND, "0.5"), {"fun_name": "f"}),
    ((BACKEND, float("nan")), {}),
    ((BACKEND, float("inf")), {}),
    ((BACKEND, -1.0), {"fun_name": "f"}),
    ((None, 1.0), {}),
    ((12, 1.0, "extra", object()), {"fun_name": object()}),
    ((LOWER, 1.0), {"fun_name": None, "other": [1]}),
])
def test_a_malformed_event_does_not_raise(tracer, args, kw):
    platform._record_compile(*args, **kw)
    for e in _named(tracer, *PHASES):
        assert e["dur"] >= 0 and isinstance(e["args"]["fun"], str)


def test_the_cache_answer_goes_to_the_next_backend_compile_only(
        tracer, monkeypatch):
    monkeypatch.setattr(platform, "CACHE_EVENTS",
                        dict.fromkeys(platform.CACHE_EVENTS, 0))
    platform._count_cache_event("/jax/compilation_cache/cache_hits")
    platform._record_compile(TRACE, 0.001, fun_name="t")
    platform._record_compile(BACKEND, 0.002, fun_name="a")
    platform._record_compile(BACKEND, 0.002, fun_name="b")
    platform._count_cache_event("/jax/compilation_cache/cache_misses")
    platform._record_compile(BACKEND, 0.002, fun_name="c")
    got = {e["args"]["fun"]: e["args"].get("cache")
           for e in _named(tracer, *PHASES)}
    assert got == {"t": None, "a": "hit", "b": None, "c": "miss"}
    assert list(platform.CACHE_EVENTS.values()) == [1, 1]


def test_a_recorded_span_is_stored_as_given_and_not_by_a_disabled_tracer():
    tr = trace_mod.Tracer(capacity=8)
    tr.record("late", "compile", 1_000, 4_000, fun="f")
    (ev,) = tr.events()
    assert (ev["name"], ev["cat"], ev["ts"], ev["dur"], ev["args"]) == \
        ("late", "compile", 1.0, 3.0, {"fun": "f"})
    off = trace_mod.Tracer(enabled=False)
    off.record("late", "compile", 1_000, 4_000)
    assert off.span_count == 0


# --------------------------------------------------- the serve engine


def _engine(cfg, **kw):
    mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    params = init_params(cfg, mesh, seed=0)
    return PagedServeEngine(cfg, mesh, slots=2, max_seq=16, prompt_pad=4,
                            page_tokens=4, **kw), params


@pytest.mark.parametrize("cfg,kw,programs", [
    (TINY_TF, {"decode_k": 4}, ["prefill", "decode_k4"]),
    (TINY_TF, {"decode_k": 4, "adapt_ladder": (4, 2), "dtype": jnp.bfloat16},
     ["prefill", "decode_k4", "decode_k2"]),
    (TINY_TF, {"decode_k": 2, "speculate_k": 3},
     ["prefill", "decode_k2", "verify"]),
    (TINY_SDAR, {}, ["prefill", "denoise_b4"]),
], ids=["plain", "ladder_bf16", "speculate", "blocks"])
def test_the_engine_names_its_set_up(tracer, cfg, kw, programs):
    engine, params = _engine(cfg, **kw)
    engine.warmup(params)
    (init,) = _named(tracer, "model_init")
    (built,) = _named(tracer, "engine_init")
    (warm,) = _named(tracer, "warmup")
    assert init["cat"] == built["cat"] == "init"
    assert init["ts"] + init["dur"] <= built["ts"] <= warm["ts"]
    runs = _named(tracer, "warmup_program")
    assert [e["args"]["program"] for e in runs] == programs
    assert sorted(programs) == sorted(engine._programs)
    for e in runs:
        assert _inside(e, warm)
        # the program's own compile phases under its run
        assert {c["name"] for c in _named(tracer, *PHASES)
                if _inside(c, e)} >= {TRACE_SPAN, LOWER_SPAN}
    resident = _named(tracer, "weights_resident")
    if kw.get("dtype") is jnp.bfloat16:
        assert len(resident) == 1
    assert all(_inside(r, warm) and not any(_inside(r, e) for e in runs)
               for r in resident)


def test_after_warmup_dispatches_make_no_compile_event(tracer):
    """As the serve loop runs them: a fresh state, an admission, then
    decode dispatches, every one a program warmup already ran."""
    engine, params = _engine(TINY_TF, decode_k=2)
    engine.warmup(params)
    calls = []

    def count(event, duration, **kw):
        calls.append(event)
    jax.monitoring.register_event_duration_secs_listener(count)
    n = len(_named(tracer, *PHASES))
    try:
        state = engine.init_state()
        state, first = engine.prefill(params, state,
                                      np.arange(4, dtype=np.int32)[None, :],
                                      3, 0, 9)
        jax.device_get(first)
        for _ in range(4):
            state, toks, valid = engine.decode(params, state)
            jax.device_get((toks, valid))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert [c for c in calls if c in platform.COMPILE_SPANS] == []
    assert len(_named(tracer, *PHASES)) == n


# ------------------------------------------------------- the reader


METRICS = ("setup_ms.start", "setup_ms.trace", "setup_ms.compile",
           "setup_ms.init", "setup_ms.warmup", "setup_ms.unattributed")
SPEC = {m: json.load(open(os.path.join(REPO, "perfbench", "metrics",
                                       m + ".json"))) for m in METRICS}


def _read(view, start_us, monkeypatch):
    monkeypatch.setattr(setup_by_span, "process_start_us", lambda: start_us)
    out = {}
    for m in METRICS:
        assert SPEC[m]["reader"] == "setup_by_span"
        out[m] = setup_by_span.read(view, SPEC[m]["params"], {})
    return out


def _span(name, t0, t1):
    return {"name": name, "t0_us": float(t0), "t1_us": float(t1),
            "args": {}}


SERVE_SETUP = [
    _span("model_init", 1_000, 3_000),
    _span("compile_trace", 1_200, 1_500),
    _span("compile_trace", 1_300, 1_400),       # an inner jit's trace
    _span("compile_lower", 1_500, 1_700),
    _span("compile_backend", 1_700, 2_500),
    _span("engine_init", 3_100, 3_200),
    _span("warmup", 3_300, 9_000),
    _span("weights_resident", 3_300, 4_000),
    _span("warmup_program", 4_100, 8_500),
    _span("compile_trace", 4_200, 5_000),
    _span("compile_lower", 5_000, 6_000),
    _span("compile_backend", 6_000, 8_000),
    _span("decode_step", 10_000, 12_000),       # the window's own
]


def test_the_six_metrics_partition_set_up(monkeypatch):
    view = {"spans": SERVE_SETUP, "window_us": (9_500.0, 20_000.0)}
    got = _read(view, 0.0, monkeypatch)
    assert got == pytest.approx({
        "setup_ms.start": 1.0,
        "setup_ms.trace": 0.3 + 0.2 + 0.8 + 1.0,
        "setup_ms.compile": 0.8 + 2.0,
        "setup_ms.init": 0.2 + 0.5 + 0.1 + 0.7,
        "setup_ms.warmup": 0.1 + 0.1 + 0.5 + 0.5,
        "setup_ms.unattributed": 0.1 + 0.1 + 0.5})
    assert sum(got.values()) == pytest.approx(9.5)


def test_nested_compile_phases_count_once(monkeypatch):
    spans = [_span("compile_trace", 100, 900), _span("compile_trace",
                                                     200, 800),
             _span("compile_trace", 300, 400), _span("compile_lower",
                                                     900, 1_000)]
    view = {"spans": spans, "window_us": (1_000.0, 2_000.0)}
    got = _read(view, 0.0, monkeypatch)
    assert got["setup_ms.trace"] == pytest.approx(0.9)
    assert got["setup_ms.start"] == pytest.approx(0.1)
    assert sum(got.values()) == pytest.approx(1.0)


@pytest.mark.parametrize("spans,start", [
    ([], 10.0),
    ([_span("weights_resident", 5_000, 6_000)], 5.0),
    ([_span("decode_step", 10_000, 12_000)], 10.0),
], ids=["no_span", "resident_only", "window_only"])
def test_a_program_without_the_spans_reads_numbers(monkeypatch, spans,
                                                   start):
    view = {"spans": spans, "window_us": (10_000.0, 20_000.0)}
    got = _read(view, 0.0, monkeypatch)
    assert all(isinstance(v, float) for v in got.values())
    assert got["setup_ms.start"] == pytest.approx(start)
    assert got["setup_ms.trace"] == got["setup_ms.compile"] == 0.0
    assert sum(got.values()) == pytest.approx(10.0)


def test_without_proc_the_reader_reads_nothing(monkeypatch):
    # without /proc ``process_age_s`` counts from its module's import,
    # which the reader's own import places after the window opened
    now_us = time.perf_counter() * 1e6
    monkeypatch.setattr(setup_by_span.run_lib, "process_age_s",
                        lambda: 1e-3)
    view = {"spans": SERVE_SETUP, "window_us": (now_us - 5e6, now_us)}
    assert {m: setup_by_span.read(view, SPEC[m]["params"], {})
            for m in METRICS} == dict.fromkeys(METRICS)


@pytest.mark.parametrize("seed", range(4))
def test_the_sweep_books_as_layers_innermost_does(seed):
    rng = np.random.default_rng(seed)
    spans = []
    for i in range(60):
        t0 = float(rng.integers(0, 1_000))
        t1 = t0 + float(rng.integers(0, 300))
        spans.append((t0, t1, f"s{i % 7}"))
    spans += [(100.0, 200.0, "tie_a"), (100.0, 200.0, "tie_b")]
    lo, hi = 50.0, 1_100.0
    got = setup_by_span.innermost(lo, hi, spans)
    want = layers.innermost([(lo, hi)], spans)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(hi - lo)


def test_every_cell_reports_the_six_and_their_names_are_disjoint():
    man = manifest.load()
    entries = {m["name"]: m for m in man["per_layer"]
               if m["name"] in METRICS}
    assert sorted(entries) == sorted(METRICS)
    for m in entries.values():
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("ms", "lower", "program_span", "set-up",
                                "setup_s")
    # due wherever ``setup_s`` is: in every cell, later ones too
    for w in man["workloads"]:
        assert set(METRICS) <= set(manifest.per_layer(man, w["name"]))
    names = [n for m in METRICS for n in SPEC[m]["params"].get("names", ())]
    assert len(names) == len(set(names))
    assert set(PHASES) <= set(names)
    assert {"model_init", "engine_init", "warmup", "warmup_program",
            "weights_resident"} <= set(names)
    np.testing.assert_equal(
        [bool(SPEC[m]["params"].get("before_first")) for m in METRICS],
        [True, False, False, False, False, False])
