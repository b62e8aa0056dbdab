"""The ``cohere2moe`` model against its plain reference
(``perfbench/lib/reference_cohere2moe.py``: float32 at ``highest``, no
cache, no grouped products, weights drawn there from the seed) at tiny
widths on the CPU: the full forward, prefill then decode through
``PagedServeEngine`` over both kinds of cache, the experts' shares, the
dropless dispatch under skew, the planted faults, the allocator.

The tolerance: program and reference compute the same float32 mathematics
in another order (grouped against per-expert products, a ring against a
band mask), which moved logits of size ~3 by at most 2e-6 in every case
below. ``TOL`` leaves that ten times of room; the same program in
bfloat16 misses it by three orders (``test_bfloat16_is_outside``), and
every planted fault by more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import reference_cohere2moe as ref
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.models import cohere2moe as M
from tpudist.models import get_model
from tpudist.parallel.mesh import build_mesh
from tpudist.serve import kvcache
from tpudist.serve.engine import PagedServeEngine, init_params

jax.config.update("jax_default_matmul_precision", "highest")

TOL = 2e-5
SEED = 5
WINDOW, RING_MARGIN, PAGE = 6, 2, 4


def configs(layers=4, held=4, first=0):
    """The same tiny model as the program's config and the reference's."""
    file = {"hidden_size": 32, "num_attention_heads": 8,
            "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 16,
            "num_experts": held, "expert_first": first,
            "num_experts_routed": 16, "num_experts_per_tok": 4,
            "num_shared_experts": 2, "vocab_size": 64,
            "num_hidden_layers": layers,
            "layer_types": (["sliding_attention"] * 3
                            + ["full_attention"]) * (layers // 4),
            "sliding_window": WINDOW, "rope_theta": 50000,
            "layer_norm_eps": 1e-5, "logit_scale": 0.5}
    cfg = ModelConfig(
        name="cohere2moe", vocab_size=64, n_layers=layers, d_model=32,
        n_heads=8, n_kv_heads=2, head_dim=8, d_ff=16, n_experts=16,
        n_experts_held=held, expert_first=first, expert_top_k=4,
        n_shared_experts=2, sliding_window=WINDOW, rope_theta=50000.0,
        logit_scale=0.5)
    return cfg, file


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 64, (n,)).astype(np.int32)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(ParallelConfig(), devices=jax.devices()[:1])


# ------------------------------------------------- (a) the full forward


@pytest.mark.parametrize("layers", [4, 8], ids=["one_period", "two_periods"])
def test_forward_logits_match_the_reference(layers):
    cfg, file = configs(layers)
    params = f32(M.init(jax.random.PRNGKey(SEED), cfg))
    t = tokens(23)       # crosses the window of 6 three times over
    got = M.apply(params, jnp.asarray(t)[None], cfg, dtype=jnp.float32)[0]
    want = ref.logits(SEED, file, t)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < TOL


def test_bfloat16_is_outside_the_tolerance():
    cfg, file = configs()
    params = M.init(jax.random.PRNGKey(SEED), cfg)      # bfloat16 at rest
    t = tokens(23)
    got = M.apply(params, jnp.asarray(t)[None], cfg, dtype=jnp.bfloat16)[0]
    assert float(jnp.abs(got - ref.logits(SEED, file, t)).max()) > 100 * TOL


def test_weights_are_made_at_rest_in_bfloat16_leaf_by_leaf(mesh):
    cfg, file = configs()
    params = init_params(cfg, mesh, seed=SEED)
    assert {a.dtype for a in jax.tree.leaves(params)
            if a.ndim > 1} == {jnp.dtype(jnp.bfloat16)}
    w = ref.layer_weights(jax.random.PRNGKey(SEED), file, 2)
    for name, leaf in w.items():
        np.testing.assert_array_equal(
            np.asarray(params["layers"][2][name], np.float32),
            np.asarray(leaf))
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(ref.embed_weights(SEED, file)))


# ------------------------------------- (b) prefill, then decode, logits


class Probe(PagedServeEngine):
    """The engine, with every logit it samples from handed to the host."""

    seen: list

    def _tied_logits(self, params, h):
        logits = super()._tied_logits(params, h)
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)),
                           logits, ordered=True)
        return logits


@pytest.fixture(scope="module")
def served(mesh):
    """Three slots of unequal length (the prompts 13, 5 and 16 tokens of a
    pad of 16) decoded 6 dispatches of 4: contexts of up to 40 cross the
    window of 6, wrap the ring of 8 at least three times and cross every
    page edge of 4."""
    cfg, file = configs()
    params = f32(init_params(cfg, mesh, seed=SEED))
    eng = Probe(cfg, mesh, slots=3, max_seq=44, prompt_pad=16, decode_k=4,
                page_tokens=PAGE, pages=30, dtype=jnp.float32,
                ring_margin=RING_MARGIN)
    eng.seen = []
    eng.warmup(params)
    eng.seen.clear()
    state, alloc = eng.init_state(), eng.new_allocator()
    prompts = {0: tokens(13, 1), 1: tokens(5, 2), 2: tokens(16, 3)}
    out = {s: {"prompt": p, "tokens": [], "logits": []}
           for s, p in prompts.items()}
    for slot, p in prompts.items():
        padded = np.zeros(16, np.int32)
        padded[:len(p)] = p
        assert alloc.admit(slot, len(p))
        state, first = eng.prefill(params, state, padded, len(p), slot, 30)
        out[slot]["tokens"].append(int(first))
        out[slot]["logits"].append(eng.seen.pop()[0])
        out[slot]["prefill_stats"] = eng.read_stats(state)
    for _ in range(6):
        for slot, p in prompts.items():
            assert alloc.ensure(slot, len(p) + len(out[slot]["tokens"]) + 2)
        state, toks, valid = eng.decode(params, state, 4)
        toks, valid = np.asarray(toks), np.asarray(valid)
        assert valid.all()
        for step, lg in enumerate(eng.seen):
            for slot in prompts:
                out[slot]["tokens"].append(int(toks[step, slot]))
                out[slot]["logits"].append(lg[slot])
        eng.seen.clear()
    eng.assert_two_programs()
    return file, out, eng, alloc, eng.read_stats(state)


@pytest.mark.parametrize("slot", [0, 1, 2])
def test_prefill_then_decode_logits_match_the_full_forward(served, slot):
    file, out, *_ = served
    o = out[slot]
    n = len(o["prompt"])
    seq = np.concatenate([o["prompt"], o["tokens"][:-1]]).astype(np.int32)
    assert len(seq) >= n + 3 * (WINDOW + RING_MARGIN)   # the ring wrapped
    want = ref.logits(SEED, file, seq)[n - 1:]
    got = np.stack(o["logits"])
    assert got.shape == want.shape
    assert float(np.abs(got - np.asarray(want)).max()) < TOL
    np.testing.assert_array_equal(np.argmax(got, -1), o["tokens"])


def test_programs_count_the_pairs_of_the_held_experts(served):
    file, out, eng, alloc, last = served
    # 4 of 16 experts held, top-4: one pair a token a layer is expected
    for slot, o in out.items():
        n = len(o["prompt"])
        st = o["prefill_stats"]
        assert 0 < st["moe_pairs_local"] <= 4 * 4 * n
        assert st["moe_pairs_per_expert"] == st["moe_pairs_local"] / 16
        # a block an expert hit at least, a layer a forward
        assert 0 < st["moe_experts_hit"] <= st["moe_blocks"] <= 4 + n // 16
    assert 0 < last["moe_pairs_local"] <= 4 * 4 * 4 * 3
    assert last["moe_pairs_per_expert"] == last["moe_pairs_local"] / 64
    assert 0 < last["moe_experts_hit"] <= 4
    # three tokens a step fit one block an expert: no second trip
    assert last["moe_blocks"] == last["moe_experts_hit"]
    # both kinds of state, as the allocator accounts them
    assert alloc.pages_used() == sum(
        -(-(len(o["prompt"]) + 24) // PAGE) for o in out.values())
    assert alloc.window_tokens_used() == 3 * (WINDOW + RING_MARGIN)


# --------------------------------------------------- (c) the shares add up


def test_the_eight_shares_and_the_shared_experts_once_give_the_uncut_layer():
    """Chip c of 8 holds experts 2c and 2c+1 of 16; what every chip
    computes alike (the shared experts) counted once."""
    cfg_all, file_all = configs(held=16)
    w = ref.layer_weights(jax.random.PRNGKey(SEED), file_all, 1)
    y = jax.random.normal(jax.random.PRNGKey(1), (37, 32), jnp.float32)
    want = ref._routed(y, w, file_all, None, None) \
        + ref._shared(y, w, file_all, None, None)
    total = jnp.zeros_like(y)
    for c in range(8):
        cfg, _ = configs(held=2, first=2 * c)
        lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][1]
        np.testing.assert_array_equal(np.asarray(lp["e_up"]),
                                      np.asarray(w["e_up"][2 * c:2 * c + 2]))
        top_e, top_w = M._route(y, lp, cfg)
        total = total + M._routed(y, top_e, top_w, lp, cfg)[0]
    total = total + M._shared(y, lp, cfg)
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(total - want).max()) < TOL


# ------------------------------------------------ (d) dropless under skew


def dense_routed(y, top_e, top_w, lp, n_held):
    out = jnp.zeros_like(y)
    for e in range(n_held):
        w_e = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        h = (jax.nn.silu(y @ lp["e_gate"][e]) * (y @ lp["e_up"][e])) \
            @ lp["e_down"][e]
        out = out + h * w_e[:, None]
    return out


@pytest.mark.parametrize("case", ["one_held_expert_takes_every_token",
                                  "every_pair_is_local",
                                  "every_pair_is_absent",
                                  "the_rows_that_are_none_route_nowhere"])
def test_no_pair_of_a_held_expert_is_dropped(case):
    cfg, _ = configs()
    lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][0]
    n = 1100                       # an expert's rows span three blocks
    y = jax.random.normal(jax.random.PRNGKey(2), (n, 32), jnp.float32)
    top_w = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(3), (n, 4)), axis=-1)
    real = None
    if case == "one_held_expert_takes_every_token":
        top_e = jnp.tile(jnp.asarray([[9, 2, 14, 7]], jnp.int32), (n, 1))
        pairs, hit = n, 1
    elif case == "every_pair_is_local":
        top_e = jnp.tile(jnp.asarray([[3, 0, 2, 1]], jnp.int32), (n, 1))
        pairs, hit = 4 * n, 4
    elif case == "every_pair_is_absent":
        top_e = jnp.tile(jnp.asarray([[9, 4, 14, 7]], jnp.int32), (n, 1))
        pairs, hit = 0, 0
    else:
        top_e = jax.random.randint(jax.random.PRNGKey(4), (n, 1), 0, 13) \
            + jnp.arange(4)[None, :]
        real = jnp.arange(n) % 3 != 0
        pairs = hit = None
    got, stats = jax.jit(lambda *a: M._routed(*a, lp, cfg, real))(
        y, top_e, top_w)
    if real is not None:
        top_w = jnp.where(real[:, None], top_w, 0.0)
        pairs = int(((top_e < 4) & real[:, None]).sum())
        hit = 4
    want = dense_routed(y, top_e, top_w, lp, 4)
    assert float(jnp.abs(got - want).max()) < TOL
    # the third count: blocks of rows run (1100 rows of one expert span
    # three blocks of 512)
    e = np.asarray(top_e)
    ok = (e < 4) if real is None else (e < 4) & np.asarray(real)[:, None]
    blocks = int((-(-np.bincount(e[ok], minlength=4) // 512)).sum())
    assert [int(v) for v in stats] == [pairs, hit, blocks]
    assert blocks == {"one_held_expert_takes_every_token": 3,
                      "every_pair_is_local": 12,
                      "every_pair_is_absent": 0}.get(case, blocks)
    if pairs:
        assert float(jnp.abs(want).max()) > 0.05


# ------------------------------------------------- (e) the planted faults


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_outside_the_tolerance(fault):
    cfg, file = configs()
    params = f32(M.init(jax.random.PRNGKey(SEED), cfg))
    t = tokens(23)
    got = M.apply(params, jnp.asarray(t)[None], cfg, dtype=jnp.float32)[0]
    wrong = ref.logits(SEED, file, t, fault=fault)
    assert float(jnp.abs(got - wrong).max()) > 1000 * TOL


def test_the_fp8_control_is_outside_the_tolerance():
    cfg, file = configs()
    t = tokens(23)
    low = ref.logits(SEED, file, t, mode="fp8")
    assert float(jnp.abs(low - ref.logits(SEED, file, t)).max()) > 1000 * TOL


# ---------------------------------------------------- (f) the allocator


def spec_for(pages):
    cfg, _ = configs()
    return kvcache.PagedCacheSpec.from_model(
        cfg, slots=3, max_seq=44, page_tokens=PAGE, pages=pages,
        ring_margin=RING_MARGIN)


def test_spec_splits_the_layers_by_the_kind_of_state_they_keep():
    spec = spec_for(30)
    assert (spec.n_layers, spec.window_layers, spec.ring_tokens) == (1, 3, 8)
    assert spec.pool_shape == (1, 2, 31, PAGE, 8)
    assert spec.ring_shape == (2, 3, 8, 8)
    assert spec.window_bytes == 2 * 3 * 3 * 2 * 8 * 8 * 4
    assert spec.bytes == 2 * 31 * 2 * PAGE * 8 * 4 + spec.table_bytes \
        + spec.window_bytes
    dense, _ = configs()
    plain = kvcache.PagedCacheSpec.from_model(
        ModelConfig(name="transformer", n_layers=4, d_model=32, n_heads=8,
                    n_kv_heads=2), slots=3, max_seq=44, page_tokens=PAGE)
    assert (plain.n_layers, plain.window_layers, plain.window_bytes) \
        == (4, 0, 0) and plain.head_dim == 4


def test_allocator_accounts_both_kinds_and_leaks_neither():
    alloc = kvcache.PageAllocator(spec_for(16))
    assert alloc.admit(0, 13) and alloc.admit(1, 5)
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (4 + 2, 8 + 5)
    assert alloc.ensure(1, 9)            # grows both kinds
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (4 + 3, 8 + 8)
    assert alloc.ensure(0, 40)           # a ring never holds more than it is
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (11 + 3, 16)
    alloc.free_slot(0)
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (3, 8)
    alloc.free_slot(1)
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (0, 0)
    assert sorted(alloc.free) == list(range(16))
    assert (alloc.table == -1).all() and not alloc.refcount.any()


def test_admission_waits_for_the_full_pool_not_for_the_rings():
    alloc = kvcache.PageAllocator(spec_for(6))
    assert alloc.admit(0, 16)                    # 4 of 6 pages
    assert not alloc.admit(1, 12)                # 3 more: refused, whole
    assert (alloc.pages_used(), alloc.window_tokens_used()) == (4, 8)
    assert (alloc.table[1] == -1).all() and alloc.window_held[1] == 0
    assert alloc.admit(1, 8)
    assert not alloc.ensure(1, 12)               # the pool is full
    assert alloc.window_held[1] == 8
    assert alloc.can_ever_admit(24, False)
    assert not alloc.can_ever_admit(25, False)   # 7 pages: never


# ------------------------------------------------ the scheduler's part


def test_run_serve_carries_the_counts_on_its_spans(mesh):
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched
    cfg, _ = configs()
    params = init_params(cfg, mesh, seed=SEED)
    eng = PagedServeEngine(cfg, mesh, slots=2, max_seq=44, prompt_pad=16,
                           decode_k=4, page_tokens=PAGE, pages=20,
                           dtype=jnp.float32, ring_margin=RING_MARGIN)
    # the tracer first: the engine says its ``experts_path`` at the first
    # dispatch a tracer sees, which is the warm-up's
    tracer = trace_lib.configure(enabled=True)
    eng.warmup(params)
    reqs = []
    for i, n in enumerate((13, 5, 16, 9)):
        t = np.zeros(16, np.int32)
        t[:n] = tokens(n, 10 + i)
        reqs.append(sched.Request(rid=i, arrival_s=0.0, tokens=t,
                                  prompt_len=n, max_new=11 + i))
    try:
        summary = sched.run_serve(eng, params, reqs)
        spans = tracer.events()
    finally:
        trace_lib.configure(enabled=False)
    eng.assert_two_programs()
    assert summary["completed"] == 4 and summary["truncated"] == 0
    assert [summary["results"][i]["generated"] for i in range(4)] \
        == [11, 12, 13, 14]
    steps = [s["args"] for s in spans if s["name"] == "decode_step"]
    fills = [s["args"] for s in spans if s["name"] == "prefill"]
    assert len(fills) == 4 and len(steps) == summary["dispatches"]
    for a in steps:
        assert {"moe_pairs_local", "moe_pairs_per_expert",
                "moe_experts_hit", "moe_blocks", "kv_full_pages",
                "kv_window_tokens", "active"} <= set(a)
        assert a["moe_blocks"] >= a["moe_experts_hit"]
        assert 0 < a["kv_window_tokens"] <= 2 * (WINDOW + RING_MARGIN)
    for a in fills:
        assert a["moe_pairs_per_expert"] == a["moe_pairs_local"] / 16
    assert summary["kv_window_tokens_peak"] == 16
    assert summary["kv_window_tokens_total"] == 16
    assert 0 < summary["moe_pairs_per_expert_mean"] < 4
    assert summary["moe_blocks_mean"] >= summary["moe_experts_hit_mean"] > 0
    # said once, at the first traced dispatch: off the TPU the loop
    assert [s["args"] for s in spans if s["name"] == "experts_path"] \
        == [{"path": "loop", "prefill": "loop"}]
    assert 0 < summary["kv_pages_used_peak"] <= 20


def test_the_engine_refuses_what_it_does_not_build(mesh):
    cfg, _ = configs()
    assert get_model("cohere2moe") is M
    with pytest.raises(ValueError, match="speculate"):
        PagedServeEngine(cfg, mesh, slots=2, max_seq=44, prompt_pad=16,
                         page_tokens=PAGE, speculate_k=3)
