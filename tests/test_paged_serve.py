"""Paged + shared-prefix KV cache and speculative decoding (PR 16).

The acceptance pins:

* greedy token streams of the paged engine, with and without a shared
  prefix and speculative decoding, are the naive full-forward greedy
  loop's, token for token — transformer and MoE, 1- and 4-device CPU
  meshes;
* the generalized program budget holds: one prefill, one decode per
  ladder rung, plus exactly one verify program iff speculation is on;
* the host page allocator's invariants: FIFO determinism, all-or-
  nothing admission/growth rollback, refcounted shared prefix pages
  that survive eviction mid-share and NEVER underflow, copy-on-write
  fork at an exact page boundary taking zero private pages;
* admission denied by page exhaustion is backpressure (request stays
  queued) while a structurally unservable prompt is rejected — with
  the shed ledger's partition exact either way;
* the fixed-HBM headline: a pool strictly smaller in bytes than four
  slots' full capacity sustains strictly more concurrent sequences;
* the paged footprint (pool + table, trash included) is what
  serve_tick / the summary / the live Prometheus gauges report;
* the serve tuner's paged coordinates: fingerprint schema bump, cache
  validation, page/speculate axis gating, never-slower-than-start.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpudist import rules as rules_lib
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.obs import live as live_lib
from tpudist.parallel import build_mesh
from tpudist.serve import kvcache
from tpudist.serve import scheduler as sched
from tpudist.serve import tune as serve_tune
from tpudist.serve.engine import PagedServeEngine, init_params

from serve_reference import greedy_tokens

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)
TINY_MOE = ModelConfig(name="moe", vocab_size=64, n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       max_seq_len=32, n_experts=4, expert_top_k=2,
                       capacity_factor=4.0)
# 9 layers: ``paged_hidden_states`` unrolls its layer loop up to 8, so
# only a deeper model runs the ROLLED loop that carries the pool (the
# form every real depth compiles to)
DEEP_TF = dataclasses.replace(TINY_TF, n_layers=9, d_model=16, d_ff=32)
DEEP_MOE = dataclasses.replace(TINY_MOE, n_layers=9, d_model=16, d_ff=32)
CFGS = {"transformer": TINY_TF, "moe": TINY_MOE,
        "transformer-l9": DEEP_TF, "moe-l9": DEEP_MOE}


def _spec(slots=2, max_seq=16, pt=4, pages=0):
    return kvcache.PagedCacheSpec.from_model(
        TINY_TF, slots=slots, max_seq=max_seq, page_tokens=pt,
        pages=pages)


def _outputs(summary):
    return {rid: r["tokens"] for rid, r in summary["results"].items()}


class _CaptureMetrics:
    """Minimal MetricsLogger stand-in: records every log() call."""

    def __init__(self):
        self.records = []

    def log(self, **kw):
        self.records.append(kw)

    def flush(self):
        pass


# ------------------------------------------------------------------ #
# page allocator invariants (pure host, no jax compile)               #
# ------------------------------------------------------------------ #

def test_allocator_fifo_reuse_and_admission_rollback():
    alloc = kvcache.PageAllocator(_spec(slots=2, pages=3))
    assert alloc.admit(0, 8)                   # 2 pages: 0, 1
    assert list(alloc.table[0][:2]) == [0, 1]
    assert alloc.pages_used() == 2
    # all-or-nothing: slot 1 wants 2 pages, only 1 left -> rollback
    assert not alloc.admit(1, 8)
    assert alloc.pages_used() == 2
    assert (alloc.table[1] == -1).all()
    # freed pages return FIFO and are immediately reusable
    alloc.free_slot(0)
    assert alloc.pages_used() == 0
    assert alloc.admit(1, 8)
    assert list(alloc.table[1][:2]) == [2, 0]  # FIFO: 2 was never used
    # growth rollback: position 15 needs pages 2+3, only 1 page free
    assert not alloc.ensure(1, 15)
    assert list(alloc.table[1]) == [2, 0, -1, -1]
    assert alloc.ensure(1, 11)                 # 3 pages fit
    assert alloc.table[1][2] >= 0


def test_allocator_refcount_underflow_raises():
    alloc = kvcache.PageAllocator(_spec(pages=2))
    with pytest.raises(kvcache.PageAllocatorError,
                       match="underflow"):
        alloc._drop(0)                         # never held
    # a double admit into a live slot is a host bug, not a silent remap
    assert alloc.admit(0, 4)
    with pytest.raises(kvcache.PageAllocatorError,
                       match="still holding"):
        alloc.admit(0, 4)


def test_allocator_shared_prefix_survives_eviction_mid_share():
    """Refcounted sharing: slots come and go while the prefix pages
    stay cached by the registry hold; counts never underflow and the
    private pages are reusable the moment their slot frees."""
    alloc = kvcache.PageAllocator(_spec(slots=3, max_seq=16, pt=4,
                                        pages=6))
    pages = alloc.register_shared(8)           # 2 full pages
    assert pages == (0, 1) and alloc.shared_len == 8
    assert alloc.admit(0, 12, shared=True)     # shared 0,1 + private
    assert alloc.admit(1, 12, shared=True)
    assert list(alloc.refcount[:2]) == [3, 3]  # registry + 2 slots
    # eviction mid-share: slot 0 goes away, the share stays intact
    alloc.free_slot(0)
    assert list(alloc.refcount[:2]) == [2, 2]
    assert 0 not in alloc.free and 1 not in alloc.free
    assert alloc.admit(2, 12, shared=True)
    assert alloc.table[2][2] == 4              # FIFO: never-used first,
    #                                            freed page 2 queues up
    alloc.free_slot(1)
    alloc.free_slot(2)
    # all slots gone: only the registry hold remains, nothing underflowed
    assert list(alloc.refcount[:2]) == [1, 1]
    assert alloc.pages_used() == 2
    # double free of an already-empty slot is a no-op (table cleared)
    alloc.free_slot(0)
    assert alloc.pages_used() == 2


def test_allocator_register_shared_edges():
    alloc = kvcache.PageAllocator(_spec(pages=1))
    with pytest.raises(kvcache.PageAllocatorError, match="cannot hold"):
        alloc.register_shared(8)               # 2 pages > pool of 1
    assert alloc.pages_used() == 0             # rollback: nothing held
    assert alloc.register_shared(4) == (0,)
    with pytest.raises(kvcache.PageAllocatorError,
                       match="already registered"):
        alloc.register_shared(4)
    # a partial page is never shared: prefix 3 < page_tokens 4
    alloc2 = kvcache.PageAllocator(_spec(pages=2))
    assert alloc2.register_shared(3) == ()
    assert alloc2.shared_len == 0


def test_allocator_cow_fork_at_exact_page_boundary():
    """A prefix that ends EXACTLY on a page boundary has no partial
    tail: an admission whose prompt is the prefix itself takes ZERO
    private pages — pure sharing, nothing to fork."""
    alloc = kvcache.PageAllocator(_spec(slots=2, max_seq=16, pt=4,
                                        pages=4))
    alloc.register_shared(8)                   # 8 % 4 == 0: both shared
    assert alloc.shared_len == 8
    used0 = alloc.pages_used()
    assert alloc.admit(0, 8, shared=True)
    assert alloc.pages_used() == used0         # no private page taken
    assert list(alloc.table[0][:2]) == [0, 1]
    # a longer prompt forks only its tail beyond the boundary
    assert alloc.admit(1, 9, shared=True)
    assert alloc.pages_used() == used0 + 1


def test_allocator_can_ever_admit():
    alloc = kvcache.PageAllocator(_spec(slots=2, max_seq=16, pt=4,
                                        pages=3))
    alloc.register_shared(4)                   # 1 registry-held page
    assert alloc.can_ever_admit(12, shared=True)    # 3 need - 1 shared
    assert not alloc.can_ever_admit(12, shared=False)  # 3 > 3 - 1 held
    assert not alloc.can_ever_admit(16, shared=True)   # 4 - 1 > 2


def test_paged_spec_bytes_counts_pool_trash_and_table():
    spec = _spec(slots=2, max_seq=16, pt=4, pages=6)
    assert spec.max_pages_per_slot == 4
    assert spec.pool_shape == (2, 2, 7, 4, 8)  # +1 trash page
    pool_elems = 2 * 7 * 4 * 2 * 8
    assert spec.table_bytes == 2 * 4 * 4
    assert spec.bytes == 2 * pool_elems * 4 + spec.table_bytes
    # default pool = full capacity (slots x max pages)
    assert _spec(slots=2, max_seq=16, pt=4, pages=0).pages == 8
    with pytest.raises(ValueError):
        _spec(pt=0)
    with pytest.raises(ValueError):
        _spec(pt=32, max_seq=16)


# ------------------------------------------------------------------ #
# token parity: the engine, +shared prefix, +speculation vs reference #
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("model_name", list(CFGS))
@pytest.mark.parametrize("n_dev", [1, 4])
def test_paged_greedy_matches_reference(devices8, model_name, n_dev):
    """The engine's whole serve lane (scatter prefill, gather-free
    write-then-attend decode, host page table) must emit the SAME token
    streams as the naive full-forward greedy loop — per request. The
    9-layer configs hold the rolled layer loop to it."""
    cfg = CFGS[model_name]
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    engine = PagedServeEngine(cfg, mesh, slots=2, max_seq=32,
                              prompt_pad=8, decode_k=4, page_tokens=8)
    engine.warmup(params)
    reqs = sched.make_requests(5, prompt_pad=8, vocab_size=cfg.vocab_size,
                               max_new=6, rate=0.0, seed=3)
    summary = sched.run_serve(engine, params, reqs)
    engine.assert_two_programs()
    assert summary["completed"] == 5, summary["partition"]
    assert _outputs(summary) == greedy_tokens(cfg, params, reqs)


@pytest.mark.parametrize("prefix_len", [8, 12])
def test_shared_prefix_matches_reference(devices8, prefix_len):
    """One cached system prompt serving every request must not move a
    single token: shared prefix vs the naive loop over the same stream.
    prefix 8 ends exactly on the page boundary (the COW fork takes no
    private page); prefix 12 forks its partial tail by recomputation."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    shared = sched.shared_prefix_tokens(prefix_len, 64, seed=5)
    engine = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=32,
                              prompt_pad=16, decode_k=4, page_tokens=8)
    engine.warmup(params)
    reqs = sched.make_requests(6, prompt_pad=16, vocab_size=64,
                               max_new=6, rate=0.0, seed=5,
                               prefix_len=prefix_len)
    summary = sched.run_serve(engine, params, reqs, shared_prefix=shared)
    engine.assert_two_programs()
    assert summary["completed"] == 6, summary["partition"]
    assert summary["shared_prefix_len"] == prefix_len
    # the registry hold keeps the full prefix pages cached after every
    # slot has drained
    full = (prefix_len // 8) * 8
    assert engine.alloc.shared_len == full
    assert engine.alloc.pages_used() == full // 8
    assert _outputs(summary) == greedy_tokens(TINY_TF, params, reqs)


@pytest.mark.parametrize("model_name", ["transformer", "transformer-l9"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_speculative_greedy_matches_reference(devices8, n_dev, model_name):
    """Speculation is a pure latency play: k-token n-gram drafts
    verified in ONE batched target forward must reproduce the plain
    greedy stream — accepted or rejected, no token moves: bitwise
    against the engine's own plain decode, and the naive loop's tokens."""
    cfg = CFGS[model_name]
    mesh = build_mesh(ParallelConfig(), devices=devices8[:n_dev])
    params = init_params(cfg, mesh, seed=0)
    shared = sched.shared_prefix_tokens(8, 64, seed=13)
    outs = {}
    for tag, spec_k in (("plain", 0), ("spec", 4)):
        engine = PagedServeEngine(cfg, mesh, slots=3, max_seq=32,
                                  prompt_pad=16, decode_k=4,
                                  page_tokens=8, speculate_k=spec_k)
        engine.warmup(params)
        reqs = sched.make_requests(8, prompt_pad=16, vocab_size=64,
                                   max_new=10, rate=0.0, seed=13,
                                   prefix_len=8)
        summary = sched.run_serve(engine, params, reqs,
                                  shared_prefix=shared)
        engine.assert_two_programs()
        assert summary["completed"] == 8, summary["partition"]
        outs[tag] = _outputs(summary)
        if tag == "spec":
            assert summary["verify_compiles"] == 1
            assert summary["speculate_k"] == 4
            rate = summary["spec_accept_rate"]
            assert rate is not None and 0.0 <= rate <= 1.0
    assert outs["plain"] == outs["spec"]
    assert outs["spec"] == greedy_tokens(cfg, params, reqs)


def test_program_pins_paged_and_speculative(devices8):
    """The generalized budget: 1 prefill + 1 decode per ladder rung,
    plus exactly one verify program iff speculate_k >= 2 — and the pin
    FAILS when a verify compiled that speculation did not buy."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    plain = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                             prompt_pad=4, decode_k=2, page_tokens=4)
    plain.warmup(params)
    plain.assert_two_programs()
    assert len(plain.verify_traces) == 0
    spec = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                            prompt_pad=4, decode_k=2, page_tokens=4,
                            speculate_k=2)
    spec.warmup(params)
    spec.assert_two_programs()
    assert len(spec.verify_traces) == 1
    spec.verify_traces.append(1)               # a second verify trace
    with pytest.raises(AssertionError, match="verify"):
        spec.assert_two_programs()
    with pytest.raises(ValueError, match="speculate-k"):
        PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                         prompt_pad=4, page_tokens=4, speculate_k=1)


# ------------------------------------------------------------------ #
# the pool rides the loops as a carry, never as scanned xs -> ys      #
# ------------------------------------------------------------------ #

def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _scans(jaxpr):
    """Every ``scan`` equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in _subjaxprs(eqn):
            yield from _scans(sub)


@pytest.mark.parametrize("model_name", ["transformer-l9", "moe-l9"])
@pytest.mark.parametrize("program", ["decode", "verify"])
def test_pool_is_a_loop_carry_never_scanned(devices8, program, model_name):
    """The mechanism of the in-place pool: in the paged decode and verify
    programs no array of the pool's shape, nor of one layer's page set,
    is among any scan's ``xs`` (a per-layer slice-out into a fresh
    buffer) or ``ys`` (a restack into a second pool); the pool appears
    only as a carry, which XLA updates in place."""
    cfg = CFGS[model_name]
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(cfg, mesh, seed=0)
    eng = PagedServeEngine(cfg, mesh, slots=2, max_seq=16, prompt_pad=4,
                           decode_k=2, page_tokens=4, speculate_k=3)
    state = eng.init_state()
    table = jnp.asarray(eng.alloc.table, jnp.int32)
    da = jnp.ones((eng.slots,), bool)
    with jax.set_mesh(mesh):
        if program == "decode":
            jaxpr = jax.make_jaxpr(eng._paged_decode_body,
                                   static_argnums=(2,))(
                params, state, 2, table, da)
        else:
            draft = jnp.zeros((eng.slots, eng.speculate_k - 1), jnp.int32)
            jaxpr = jax.make_jaxpr(eng._paged_verify_body)(
                params, state, draft, table, da)
    pool = tuple(eng.spec.pool_shape)
    assert pool[0] == cfg.n_layers == 9
    carried = 0
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    for eqn in _scans(jaxpr.jaxpr):
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        carried += shapes(eqn.invars[nc:nc + ncar]).count(pool)
        for what, seen in (("xs", shapes(eqn.invars[nc + ncar:])),
                           ("ys", shapes(eqn.outvars[ncar:]))):
            for shape in seen:
                assert shape not in (pool, pool[1:]), (
                    f"a scan's {what} holds a KV pool array {shape}")
    # pool_k and pool_v in the layer loop's carry (and, in the decode
    # program, in the token-step loop's around it)
    assert carried >= 2, carried


# ------------------------------------------------------------------ #
# page exhaustion: backpressure vs reject, eviction funds the batch   #
# ------------------------------------------------------------------ #

def test_page_exhaustion_backpressure_and_exact_reject(devices8):
    """A pool too full RIGHT NOW queues the request (backpressure —
    nothing shed); a prompt the pool could NEVER hold is rejected with
    reason kv_pages_exhausted — and the ledger partition stays exact."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    engine = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                              prompt_pad=12, decode_k=2, page_tokens=4,
                              pages=2)
    engine.warmup(params)

    def req(rid, prompt_len, max_new=3):
        toks = np.zeros((12,), np.int32)
        toks[:prompt_len] = (np.arange(prompt_len) * 5 + rid) % 64
        return sched.Request(rid=rid, arrival_s=0.0, tokens=toks,
                             prompt_len=prompt_len, max_new=max_new)

    # rid 0 needs 3 pages > the 2-page pool: structurally unservable.
    # rids 1 and 2 need 2 pages each: only one fits at a time, so rid 2
    # must WAIT while rid 1 runs, then complete — never be shed.
    metrics = _CaptureMetrics()
    summary = sched.run_serve(engine, params,
                              [req(0, 12), req(1, 5), req(2, 5)],
                              metrics=metrics, tick_every=1)
    engine.assert_two_programs()
    part = summary["partition"]
    assert part["admission_exact"] and part["outcome_exact"], part
    assert summary["rejected"] == 1
    assert summary["shed_at_admission"] == 0
    assert summary["completed"] == 2 and summary["truncated"] == 0
    assert sorted(summary["results"]) == [1, 2]
    rejects = [r for r in metrics.records
               if r.get("kind") == "serve_request"
               and r.get("event") == "rejected"]
    assert len(rejects) == 1 and rejects[0]["rid"] == 0
    assert rejects[0]["reason"] == "kv_pages_exhausted"
    # the run drained: every page is back in the pool
    assert engine.alloc.pages_used() == 0


def test_growth_failure_evicts_and_frees_pages(devices8):
    """Two slots racing for a pool that can only grow one: the loser is
    evicted (truncated, pages freed) and the winner runs to completion
    on the freed pages — the partition stays exact, the pool drains."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    engine = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                              prompt_pad=4, decode_k=4, page_tokens=4,
                              pages=3)
    engine.warmup(params)

    def req(rid):
        toks = ((np.arange(4) * 3 + rid + 1) % 64).astype(np.int32)
        return sched.Request(rid=rid, arrival_s=0.0, tokens=toks,
                             prompt_len=4, max_new=8)

    summary = sched.run_serve(engine, params, [req(0), req(1)])
    engine.assert_two_programs()
    part = summary["partition"]
    assert part["admission_exact"] and part["outcome_exact"], part
    assert summary["truncated"] == 1 and part["evicted"] == 1
    assert summary["completed"] == 2           # evicted still returns
    done = [r for r in summary["results"].values() if r["why"] == "done"]
    assert len(done) == 1 and done[0]["generated"] == 8
    assert engine.alloc.pages_used() == 0


# ------------------------------------------------------------------ #
# the fixed-HBM headline: more concurrency in fewer bytes             #
# ------------------------------------------------------------------ #

def test_fixed_hbm_small_pool_sustains_more_slots(devices8):
    """The tentpole's acceptance: a pool STRICTLY smaller in bytes than
    four slots at full capacity (trash page and page table included)
    sustains STRICTLY more concurrent sequences under the same
    shared-prefix load."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    # make_requests derives the in-prompt prefix from ITS seed — the
    # registered prefix must use the same one or no prompt byte-matches
    shared = sched.shared_prefix_tokens(8, 64, seed=21)
    full = PagedServeEngine(TINY_TF, mesh, slots=4, max_seq=32,
                            prompt_pad=16, decode_k=8, page_tokens=8)
    # full capacity = 16 pages (4 slots x 32/8); the small pool holds 6
    # slots in 14 pages: worst case 6 x 2 private pages (final length
    # <= 24 -> 3 pages, 1 of them shared) + 1 shared
    small = PagedServeEngine(TINY_TF, mesh, slots=6, max_seq=32,
                             prompt_pad=16, decode_k=8, page_tokens=8,
                             pages=14)
    assert full.spec.pages == 16
    assert small.spec.bytes < full.spec.bytes, (
        small.spec.bytes, full.spec.bytes)
    peaks = {}
    for tag, engine, prefix in (("full", full, None),
                                ("small", small, shared)):
        engine.warmup(params)
        reqs = sched.make_requests(16, prompt_pad=16, vocab_size=64,
                                   max_new=8, rate=0.0, seed=21,
                                   prefix_len=8)
        summary = sched.run_serve(engine, params, reqs,
                                  shared_prefix=prefix)
        engine.assert_two_programs()
        assert summary["completed"] == 16, summary["partition"]
        peaks[tag] = summary["active_slots_peak"]
        assert summary["kv_pages_used_peak"] <= engine.spec.pages
    assert peaks["small"] > peaks["full"], peaks


# ------------------------------------------------------------------ #
# observability: serve_tick footprint, summary fields, live gauges    #
# ------------------------------------------------------------------ #

def test_serve_tick_and_summary_report_paged_footprint(devices8):
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    engine = PagedServeEngine(TINY_TF, mesh, slots=2, max_seq=16,
                              prompt_pad=4, decode_k=2, page_tokens=4,
                              speculate_k=2)
    engine.warmup(params)
    reqs = sched.make_requests(4, prompt_pad=4, vocab_size=64,
                               max_new=4, rate=0.0, seed=7)
    metrics = _CaptureMetrics()
    summary = sched.run_serve(engine, params, reqs, metrics=metrics,
                              tick_every=1)
    ticks = [r for r in metrics.records if r["kind"] == "serve_tick"]
    assert ticks, "no serve_tick records"
    for t in ticks:
        # the PAGED footprint — pool + table, not slots x max_seq
        assert t["kv_cache_bytes"] == engine.spec.bytes
        assert t["kv_pages_total"] == engine.spec.pages
        assert 0 <= t["kv_pages_used"] <= engine.spec.pages
    assert summary["kv_page_tokens"] == 4
    assert summary["kv_pages_total"] == engine.spec.pages
    assert summary["kv_pages_used_peak"] >= 1
    assert summary["spec_accept_rate"] is not None


def test_spec_accept_rule_in_rules_table(monkeypatch):
    rule = rules_lib.get("spec_accept")
    assert rule.sense == "min" and not rule.alert
    assert rules_lib.resolve("spec_accept") == 0.0
    monkeypatch.setenv("TPUDIST_SERVE_SPEC_ACCEPT_MIN", "0.5")
    assert rules_lib.resolve("spec_accept") == 0.5
    # never a live alert: the golden Prometheus alert series is pinned
    assert "spec_accept" not in {t.name for t in rules_lib.ALERT_RULES}


def test_live_gauges_ingest_and_render(tmp_path):
    """Consumer parity for the three paged gauges: a serve_tick record
    flows through the aggregator into /metrics; a tick without those
    keys renders none of them."""
    agg = live_lib.LiveAggregator(out_dir=str(tmp_path),
                                  start_ticker=False)
    agg.ingest({"kind": "serve_tick", "completed": 2,
                "kv_pages_used": 5, "kv_pages_total": 24,
                "spec_accept_rate": 0.75})
    snap = agg.snapshot()
    sv = snap["pod"]["serve"]
    assert sv["kv_pages_used"] == 5 and sv["kv_pages_total"] == 24
    assert sv["spec_accept_rate"] == 0.75
    text = live_lib.prometheus_text(snap)
    assert "tpudist_serve_kv_pages_used 5" in text
    assert "tpudist_serve_kv_pages_total 24" in text
    assert "tpudist_serve_spec_accept_rate 0.75" in text
    # absent keys render nothing (the golden exposition is safe)
    agg2 = live_lib.LiveAggregator(out_dir=str(tmp_path / "d"),
                                   start_ticker=False)
    agg2.ingest({"kind": "serve_tick", "completed": 1,
                 "itl_p99_s": 0.1})
    text2 = live_lib.prometheus_text(agg2.snapshot())
    assert "kv_pages" not in text2 and "spec_accept" not in text2


# ------------------------------------------------------------------ #
# the draft proposer                                                  #
# ------------------------------------------------------------------ #

def test_ngram_draft_lookup_and_fallback():
    # last token 1 last occurred at index 0, followed by 2; the draft
    # then continues from its own extension (..., 2 -> 3)
    assert sched.ngram_draft([1, 2, 3, 1], 2) == [2, 3]
    # no earlier occurrence: repeat the token itself
    assert sched.ngram_draft([5], 3) == [5, 5, 5]
    # deterministic, host-only, never empty for k >= 1
    assert sched.ngram_draft([7, 7, 9], 1) == [9]


# ------------------------------------------------------------------ #
# serve tuner: paged coordinates                                      #
# ------------------------------------------------------------------ #

def test_validate_serve_tuned_paged_schema():
    ok = {"decode_k": 8, "kv_page_tokens": 8, "speculate_k": 4}
    assert serve_tune.validate_serve_tuned(ok)
    # pre-paging records are a cache MISS, never a crash
    assert not serve_tune.validate_serve_tuned({"decode_k": 8})
    assert not serve_tune.validate_serve_tuned(
        dict(ok, speculate_k=1))               # window of 1 is invalid
    assert not serve_tune.validate_serve_tuned(
        dict(ok, kv_page_tokens=0))            # a page holds a position
    assert serve_tune.validate_serve_tuned(dict(ok, speculate_k=0))
    assert not serve_tune.validate_serve_tuned(
        dict(ok, kv_page_tokens=-1))


def test_search_walks_paged_axes_with_real_win_bar():
    """The axis walk adopts a page size / speculate window only on a
    REAL measured win, and never commits a point slower than the
    measured start."""
    def measure_from(table):
        def measure(cand):
            return serve_tune.ServeProbeResult(
                tokens_per_sec=table(cand), dispatch_ms=1.0)
        return measure

    start = serve_tune.ServeCandidate(decode_k=8, kv_page_tokens=64)
    # a smaller page wins big, then speculation wins on top of it
    res = serve_tune._search(
        measure_from(lambda c: 100.0 + 50 * (c.kv_page_tokens == 16)
                     + 50 * (c.speculate_k == 4)),
        start, max_decode_k=8, trial_budget=32, max_page_tokens=64)
    assert res["best"].kv_page_tokens == 16
    assert res["best"].speculate_k == 4
    assert res["best_tps"] >= res["baseline_tps"]
    # a tie keeps the start's page and plain decode
    res = serve_tune._search(
        measure_from(lambda c: 100.0), start, max_decode_k=8,
        trial_budget=32, max_page_tokens=64)
    assert res["best"].kv_page_tokens == 64
    assert res["best"].speculate_k == 0
    # paged axes are OFF without the opt-in bound
    res = serve_tune._search(
        measure_from(lambda c: 100.0 + 500 * (c.kv_page_tokens != 64)),
        start, max_decode_k=8, trial_budget=32)
    assert res["best"].kv_page_tokens == 64
    # the hard floor: everything measures slower than start -> start
    res = serve_tune._search(
        measure_from(lambda c: 100.0 if c == start else 1.0),
        start, max_decode_k=32, trial_budget=32, max_page_tokens=64)
    assert res["best"] == start
    assert res["best_tps"] == res["baseline_tps"] == 100.0


def test_probe_candidate_paged_and_speculative(devices8):
    """The measured probe runs the real paged / speculative engines and
    counts tokens from the device's own lengths ledger."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(TINY_TF, mesh, seed=0)
    for cand in (serve_tune.ServeCandidate(decode_k=2,
                                           kv_page_tokens=8),
                 serve_tune.ServeCandidate(decode_k=2, kv_page_tokens=8,
                                           speculate_k=2)):
        res = serve_tune.probe_candidate(
            TINY_TF, mesh, params, cand, slots=2, max_seq=32,
            prompt_pad=8, n_dispatches=2, repeats=1)
        assert res.feasible, res.error
        assert res.tokens > 0 and res.tokens_per_sec > 0


def test_serve_fingerprint_distinct_from_pre_paging_schema(devices8):
    """The knob-space bump: the serve fingerprint must differ from one
    computed WITHOUT the paged axes, so stale cached records never hit."""
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    fp = serve_tune.fingerprint(TINY_TF, mesh, slots=2, max_seq=16,
                                prompt_pad=4)
    assert isinstance(fp, str) and len(fp) >= 8
    # deterministic for the same situation
    assert fp == serve_tune.fingerprint(TINY_TF, mesh, slots=2,
                                        max_seq=16, prompt_pad=4)
    assert fp != serve_tune.fingerprint(TINY_TF, mesh, slots=3,
                                        max_seq=16, prompt_pad=4)


# ------------------------------------------------------------------ #
# CLI wiring                                                          #
# ------------------------------------------------------------------ #

def test_cli_refuses_a_speculation_window_of_one(tmp_path, capsys):
    """``--speculate-k 1`` is a window with no draft in it: the engine's
    ValueError, as the CLI's fail verdict and exit code."""
    from tpudist.serve import cli
    assert cli.main(["--speculate-k", "1", "--requests", "1",
                     "--save-dir", str(tmp_path)]) == 1
    assert "--speculate-k must be 0 (off) or >= 2" in capsys.readouterr().err


@pytest.mark.slow
def test_paged_serve_cli_e2e_4dev_mesh(tmp_path):
    """``python -m tpudist.serve`` with paging + shared prefix +
    speculation on a 4-device CPU mesh: green verdict, the generalized
    program pin in the artifact, paged gauges on the tick stream."""
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "TPUDIST_VERDICT_PATH": str(tmp_path / "verdict.txt"),
        "TPUDIST_TTFT_P99_MAX": "120", "TPUDIST_ITL_P99_MAX": "60",
        "TPUDIST_TOKENS_PER_CHIP_MIN": "0.001",
    })
    env.pop("TPUDIST_STAGING_BUDGET_MB", None)
    bench = tmp_path / "BENCH_SERVE.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tpudist.serve", "--requests", "12",
         "--max-new-tokens", "8", "--request-rate", "200",
         "--kv-page-tokens", "8", "--shared-prefix", "8",
         "--speculate-k", "4",
         "--save-dir", str(tmp_path), "--bench-out", str(bench)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    assert "tpudist: serve success" in proc.stdout

    doc = json.loads(bench.read_text())
    d = doc["detail"]
    assert doc["slo"]["status"] == "success"
    assert d["prefill_compiles"] == 1 and d["decode_compiles"] == 1
    assert d["verify_compiles"] == 1
    assert d["kv_page_tokens"] == 8 and d["speculate_k"] == 4
    assert d["shared_prefix_len"] == 8
    assert d["kv_pages_used_peak"] >= 1
    assert (tmp_path / "verdict.txt").read_text().strip() == "success"
    recs = [json.loads(l) for l in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    serves = [r for r in recs if r.get("kind") == "serve"]
    assert len(serves) == 1
    assert serves[0]["verify_compiles"] == 1
    assert serves[0]["kv_pages_total"] > 0


# ------------------------------------------------------------------ #
# what a page-bounded read spares: pages mapped a dispatch (PR 31)    #
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("model_name,spec_k", [("transformer", 0),
                                               ("moe", 0),
                                               ("transformer", 3)])
def test_every_dispatch_span_carries_kv_full_pages(devices8, model_name,
                                                   spec_k):
    """Every ``decode_step`` (and ``verify_step``) span, for every model,
    notes the pages the slots' rows map; the summary carries their mean
    over the dispatches beside the peak."""
    from tpudist.obs import trace as trace_lib
    cfg = CFGS[model_name]
    mesh = build_mesh(ParallelConfig(), devices=devices8[:1])
    params = init_params(cfg, mesh, seed=0)
    engine = PagedServeEngine(cfg, mesh, slots=2, max_seq=32, prompt_pad=8,
                              decode_k=4, page_tokens=4, pages=12,
                              speculate_k=spec_k)
    engine.warmup(params)
    reqs = sched.make_requests(4, prompt_pad=8, vocab_size=cfg.vocab_size,
                               max_new=9, rate=0.0, seed=3)
    tracer = trace_lib.configure(enabled=True)
    try:
        summary = sched.run_serve(engine, params, reqs)
        spans = tracer.events()
    finally:
        trace_lib.configure(enabled=False)
    name = "verify_step" if spec_k else "decode_step"
    steps = [s["args"] for s in spans if s["name"] == name]
    assert steps and len(steps) == summary["dispatches"]
    pages = [a["kv_full_pages"] for a in steps]
    # a live slot holds at least its prompt's pages and never the pool
    assert all(1 <= p <= 12 for p in pages), pages
    assert summary["kv_pages_used_peak"] == max(pages)
    assert summary["kv_pages_used_mean"] == round(
        sum(pages) / len(pages), 2)
    assert summary["kv_pages_total"] == 12


def test_report_prints_pages_read_over_pool_pages():
    from tpudist.obs import report
    recs = [dict(kind="serve", requests=1, completed=1,
                 generated_tokens=8, wall_s=0.05,
                 tokens_per_sec_per_chip=40.0, status="success",
                 kv_pages_used_peak=61, kv_pages_used_mean=43.25,
                 kv_pages_total=256, kv_page_tokens=64,
                 ttft_p50_s=0.005, ttft_p99_s=0.005,
                 itl_p50_s=0.005, itl_p99_s=0.005)]
    rep = report.build_report(recs, {})
    assert rep["serving"]["kv_pages_used_mean"] == 43.25
    text = report.to_markdown(rep)
    assert "- kv pages read a dispatch: 43.25 of 256 pool pages" in text
    assert "peak 61" in text
    # a run record from before the counter: no line, no error
    del recs[0]["kv_pages_used_mean"]
    assert "kv pages read a dispatch" not in report.to_markdown(
        report.build_report(recs, {}))
