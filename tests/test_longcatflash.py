"""The ``longcatflash`` model against its plain reference
(``perfbench/lib/reference_longcatflash.py``: float32 at ``highest``, the
EXPANDED form of latent attention only, no cache, no pages, no grouped
products, weights drawn there from the seed) at tiny widths on the CPU:
the full forward, prefill then decode through ``PagedServeEngine`` over
the latent pages, the absorbed form against the expanded one, the
shortcut and the router by planted faults, the experts' shares, the
dropless dispatch under skew and over identity pairs, the spec of the
latent pool, what the engine refuses.

The tolerance: program and reference compute the same float32 mathematics
in another order (absorbed against expanded products, grouped against
per-expert products, a masked read of pages against a band mask), which
moved logits of size ~3 by at most 2e-6 in every case below. ``TOL``
leaves that ten times of room; the same program in bfloat16 misses it by
three orders, and every planted fault by more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import reference_longcatflash as ref
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.models import dropless, get_model, model_for
from tpudist.models import longcatflash as M
from tpudist.models import transformer as T
from tpudist.parallel.mesh import build_mesh
from tpudist.serve import kvcache
from tpudist.serve.engine import PagedServeEngine, init_params

jax.config.update("jax_default_matmul_precision", "highest")

TOL = 2e-5
SEED = 5
PAGE = 4
WIDTHS = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8)


def configs(layers=2, held=4, first=0):
    """The same tiny model as the program's config and the reference's:
    16 real experts and 8 identity ones, top-4 of 24."""
    file = {"hidden_size": 32, "num_attention_heads": 4, **WIDTHS,
            "ffn_hidden_size": 48, "expert_ffn_hidden_size": 16,
            "n_routed_experts": held, "expert_first": first,
            "n_routed_experts_routed": 16, "zero_expert_num": 8,
            "moe_topk": 4, "routed_scaling_factor": 6, "vocab_size": 64,
            "num_layers": layers, "rms_norm_eps": 1e-5,
            "rope_theta": 10000000}
    cfg = ModelConfig(
        name="longcatflash", vocab_size=64, n_layers=layers, d_model=32,
        n_heads=4, d_ff=16, d_ff_dense=48, n_experts=16,
        n_experts_held=held, expert_first=first, expert_top_k=4,
        n_zero_experts=8, routed_scaling=6.0, rope_theta=1e7,
        norm_eps=1e-5, **WIDTHS)
    return cfg, file


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 64, (n,)).astype(np.int32)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(ParallelConfig(), devices=jax.devices()[:1])


# ------------------------------------------------- (a) the full forward


@pytest.mark.parametrize("layers", [2, 3])
def test_forward_logits_match_the_reference(layers):
    cfg, file = configs(layers)
    params = f32(M.init(jax.random.PRNGKey(SEED), cfg))
    t = tokens(23)
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


@pytest.mark.parametrize("layers", [2, 4])
def test_the_references_bfloat16_witness_reads_what_the_programs_bfloat16_does(
        layers):
    """What the benchmark reads the program's gap beside: the reference's
    own mathematics with operands, results and the residual stream rounded
    to bfloat16 lies as far from the float32 reference as the program in
    bfloat16 does, to a small factor, at either depth."""
    cfg, file = configs(layers)
    t = tokens(23)
    want = ref.logits(SEED, file, t)
    witness = float(jnp.abs(ref.logits(SEED, file, t, mode="bf16")
                            - want).mean())
    got = M.apply(M.init(jax.random.PRNGKey(SEED), cfg), jnp.asarray(t)[None],
                  cfg, dtype=jnp.bfloat16)[0]
    mine = float(jnp.abs(got - want).mean())
    assert witness > 100 * TOL
    assert witness / 4 < mine < 4 * witness, (mine, witness)


def test_weights_are_made_at_rest_in_bfloat16_leaf_by_leaf(mesh):
    cfg, file = configs()
    params = init_params(cfg, mesh, seed=SEED)
    assert {a.dtype for a in jax.tree.leaves(params)
            if a.ndim > 1} == {jnp.dtype(jnp.bfloat16)}
    w = ref.layer_weights(jax.random.PRNGKey(SEED), file, 1)
    mine = params["layers"][1]
    same = lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b))
    for i in (0, 1):
        for name, leaf in w["sub"][i].items():
            same(mine["sub"][i][name], leaf)
    for name in ("w_router", "router_bias"):
        same(mine[name], w[name])
    for name in ("e_gate", "e_up", "e_down"):
        same(jnp.stack(mine[name]), w[name])
    same(params["embed"], ref.embed_weights(SEED, file))
    same(params["head"], ref.head_weights(SEED, file))
    # the selection bias is there, at the scale of a mean score
    assert 0 < float(jnp.abs(w["router_bias"]).max()) <= 1 / 24


# ------------------------------------- (b) prefill, then decode, logits


class Probe(PagedServeEngine):
    """The engine, with every logit it samples from handed to the host."""

    seen: list

    def _greedy(self, params, h):
        logits = self.model.head_logits(params, h, self.dtype)
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)),
                           logits, ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@pytest.fixture(scope="module")
def served(mesh):
    """Three slots of unequal length (prompts of 13, 5 and 16 tokens of a
    pad of 16: two end inside a page of 4) decoded 6 dispatches of 4:
    contexts of up to 40 cross every page edge."""
    cfg, file = configs()
    params = f32(init_params(cfg, mesh, seed=SEED))
    eng = Probe(cfg, mesh, slots=3, max_seq=44, prompt_pad=16, decode_k=4,
                page_tokens=PAGE, pages=30, dtype=jnp.float32)
    eng.seen = []
    eng.warmup(params)
    eng.seen.clear()
    state, alloc = eng.init_state(), eng.new_allocator()
    assert state.pool_v is None
    assert state.pool_k.shape == (4, 1, 31, PAGE, 128)
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
    return file, out, eng, alloc, eng.read_stats(state), state


@pytest.mark.parametrize("slot", [0, 1, 2])
def test_prefill_then_decode_logits_match_the_full_forward(served, slot):
    file, out, *_ = served
    o = out[slot]
    n = len(o["prompt"])
    seq = np.concatenate([o["prompt"], o["tokens"][:-1]]).astype(np.int32)
    want = ref.logits(SEED, file, seq)[n - 1:]
    got = np.stack(o["logits"])
    assert got.shape == want.shape == (25, 64)
    assert float(np.abs(got - np.asarray(want)).max()) < TOL
    np.testing.assert_array_equal(np.argmax(got, -1), o["tokens"])


def test_programs_count_local_identity_and_all_pairs(served):
    file, out, eng, alloc, last, state = served
    for o in out.values():
        n, st = len(o["prompt"]), o["prefill_stats"]
        # every real token routes top-4 a layer, the padding nowhere
        assert st["moe_pairs_all"] == 4 * n
        assert 0 < st["moe_pairs_zero"] < st["moe_pairs_all"]
        assert 0 <= st["moe_pairs_local"] <= 2 * 4 * n
        assert st["moe_pairs_per_expert"] == st["moe_pairs_local"] / 8
    # 3 slots x top-4, a layer a token step
    assert last["moe_pairs_all"] == 12
    assert 0 < last["moe_pairs_zero"] < 12
    assert last["moe_pairs_per_expert"] == last["moe_pairs_local"] / 32
    assert last["moe_blocks"] == last["moe_experts_hit"]
    assert alloc.pages_used() == sum(
        -(-(len(o["prompt"]) + 24) // PAGE) for o in out.values())
    # the dead lanes of every row written stay zero
    rows = np.asarray(state.pool_k)
    assert not rows[..., 20:].any() and rows[..., :20].any()


def test_the_absorbed_form_is_the_expanded_form():
    """One sequence as a window of 11 new tokens over empty pages (write,
    then read up to each token's own position) against the expanded causal
    forward: hidden states and the rows the cache keeps."""
    cfg, _ = configs()
    params = f32(M.init(jax.random.PRNGKey(SEED), cfg))
    n = 11
    t = jnp.asarray(tokens(n, 7))[None]
    want, rows, _ = M.prefill_hidden_states(params, t, cfg,
                                            dtype=jnp.float32)
    pool = jnp.zeros((4, 1, 5, PAGE, cfg.latent_row), jnp.float32)
    table = jnp.asarray([[2, 0, 3]], jnp.int32)
    got, pool, _ = M.paged_hidden_states(
        params, t, cfg, dtype=jnp.float32, pool=pool, page_table=table,
        positions=jnp.arange(n)[None], write_ok=jnp.ones((1, n), bool),
        page_tokens=PAGE)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < TOL
    for sub, row in enumerate(rows):
        kept = pool[sub, 0, jnp.asarray([2, 0, 3])].reshape(-1, 128)[:n]
        assert float(jnp.abs(kept - row[0]).max()) < TOL


# ----------------------------------- (c) the shortcut, the router, faults


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_outside_the_tolerance(fault):
    """``sequential_layer`` is a model whose mix is added a sublayer
    early; ``bias_in_weights``, ``scaling_dropped`` and ``zero_dropped``
    are the router's three readings gone wrong."""
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


def test_the_router_biases_the_choice_alone_and_scales_without_norming():
    cfg, _ = configs()
    lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][0]
    y = jax.random.normal(jax.random.PRNGKey(1), (50, 32), jnp.float32)
    # a bias large enough to decide the choice
    lp = dict(lp, router_bias=lp["router_bias"] * 30.0)
    top_e, top_w = M._route(y, lp, cfg)
    p = np.asarray(jax.nn.softmax(y @ lp["w_router"], axis=-1))
    biased = p + np.asarray(lp["router_bias"])
    want_e = np.argsort(-biased, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(top_e), -1),
                                  np.sort(want_e, -1))
    assert (np.sort(want_e, -1)
            != np.sort(np.argsort(-p, axis=-1)[:, :4], -1)).any()
    # the weights: the score itself, times 6, summing to no fixed number
    np.testing.assert_allclose(
        np.asarray(top_w), 6.0 * np.take_along_axis(p, np.asarray(top_e), -1),
        rtol=1e-6)
    assert np.ptp(np.asarray(top_w).sum(-1)) > 0.1


def test_identity_pairs_add_their_weights_times_the_input():
    cfg, _ = configs()
    lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][0]
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 50, 32), jnp.float32)
    out, stats = M._mix(y, lp, cfg)
    top_e, top_w = M._route(y[0], lp, cfg)
    held, _ = dropless.routed(
        y[0], top_e, top_w, (lp["e_gate"], lp["e_up"], lp["e_down"]),
        first=0, held=4, n_routed=24)
    w_zero = np.where(np.asarray(top_e) >= 16, np.asarray(top_w), 0).sum(-1)
    assert w_zero.max() > 0.5
    np.testing.assert_allclose(np.asarray(out[0] - held),
                               w_zero[:, None] * np.asarray(y[0]),
                               atol=TOL)
    assert [int(v) for v in stats[3:]] == [int((np.asarray(top_e) >= 16)
                                               .sum()), 200]


# --------------------------------------------------- (d) the shares add up


def test_four_shares_and_what_every_chip_computes_once_give_the_uncut_layer():
    """Chip c of 4 holds experts 4c .. 4c+3 of 16. Every chip computes the
    attention sublayers, the dense FFNs and the identity term alike: chip
    0's whole layer carries them once, the other chips add their routed
    parts alone."""
    cfg_all, file_all = configs(held=16)
    w = ref.layer_weights(jax.random.PRNGKey(SEED), file_all, 1)
    # (a draw on which no two experts tie for the fourth place within a
    # float32 rounding: the choice is discontinuous there)
    x = jax.random.normal(jax.random.PRNGKey(2), (19, 32), jnp.float32)
    want = ref._layer(x, w, file_all, None, None)
    pos = jnp.arange(19)
    total = None
    for c in range(4):
        cfg, _ = configs(held=4, first=4 * c)
        lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][1]
        np.testing.assert_array_equal(
            np.asarray(jnp.stack(lp["e_up"])),
            np.asarray(w["e_up"][4 * c:4 * c + 4]))
        attend = lambda i, x, sp: M._mla_expanded(x, sp, cfg, pos)[0]
        if c == 0:
            total = M._layer(x[None], lp, cfg, attend)[0][0]
            continue
        a0 = attend(0, x[None], lp["sub"][0])
        y0 = T.rmsnorm(a0, lp["sub"][0]["post_norm"], cfg.norm_eps)[0]
        top_e, top_w = M._route(y0, lp, cfg)
        total = total + dropless.routed(
            y0, top_e, top_w, (lp["e_gate"], lp["e_up"], lp["e_down"]),
            first=4 * c, held=4, n_routed=24)[0]
    assert float(jnp.abs(want - x).max()) > 0.5
    assert float(jnp.abs(total - want).max()) < TOL


# ------------------------------------------------ (e) dropless under skew


@pytest.mark.parametrize("case", ["one_held_expert_takes_every_token",
                                  "every_pair_is_an_identity_expert",
                                  "held_absent_and_identity_mixed"])
def test_no_held_pair_is_dropped_and_identity_pairs_fall_out(case):
    cfg, _ = configs()
    lp = f32(M.init(jax.random.PRNGKey(SEED), cfg))["layers"][0]
    n = 150                        # one expert's rows span five blocks
    y = jax.random.normal(jax.random.PRNGKey(2), (n, 32), jnp.float32)
    top_w = 6.0 * jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(3), (n, 4)), axis=-1)
    top_e = jnp.tile(jnp.asarray({
        "one_held_expert_takes_every_token": [[9, 2, 14, 20]],
        "every_pair_is_an_identity_expert": [[16, 23, 19, 21]],
        "held_absent_and_identity_mixed": [[3, 11, 17, 0]]}[case],
        jnp.int32), (n, 1))
    got, stats = jax.jit(lambda *a: dropless.routed(
        *a, (lp["e_gate"], lp["e_up"], lp["e_down"]), first=0, held=4,
        n_routed=24))(y, top_e, top_w)
    want = jnp.zeros_like(y)
    for e in range(4):
        w_e = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        h = (jax.nn.silu(y @ lp["e_gate"][e]) * (y @ lp["e_up"][e])) \
            @ lp["e_down"][e]
        want = want + h * w_e[:, None]
    assert float(jnp.abs(got - want).max()) < TOL
    block = dropless.block_rows(n, 4, 24)
    pairs, hit = {"one_held_expert_takes_every_token": (n, 1),
                  "every_pair_is_an_identity_expert": (0, 0),
                  "held_absent_and_identity_mixed": (2 * n, 2)}[case]
    assert [int(v) for v in stats] == [pairs, hit, hit * -(-n // block)]
    if pairs:
        assert float(jnp.abs(want).max()) > 0.05
    else:
        assert not np.asarray(got).any()


# ------------------------------------------------ (f) the latent pool


def test_spec_learns_the_latent_kind_from_the_model_config():
    cfg, _ = configs(layers=3)
    assert cfg.latent_row == 128            # 16 + 4 values in whole lanes
    spec = kvcache.PagedCacheSpec.from_model(
        cfg, slots=5, max_seq=40, page_tokens=8, pages=12,
        dtype=jnp.bfloat16)
    assert spec.latent and spec.pools == 1 and spec.window_layers == 0
    assert spec.pool_shape == (6, 1, 13, 8, 128)
    assert spec.bytes == 6 * 13 * 8 * 128 * 2 + spec.table_bytes
    cache = kvcache.init_paged_cache(spec)
    assert cache["v"] is None and cache["k"].shape == spec.pool_shape
    # the memory bound counts one pool a page
    alloc = kvcache.PageAllocator(spec)
    page = 6 * 8 * 128 * 2
    assert alloc.set_memory_bound(hbm_bytes=7.5 * page + spec.table_bytes,
                                  program_temp_bytes=0) == 7
    # the published widths: 512 + 64 values a row, stored at 640
    full = ModelConfig(
        name="longcatflash", d_model=6144, n_heads=64, n_layers=4,
        d_ff=2048, d_ff_dense=12288, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    assert full.latent_row == 640
    assert kvcache.PagedCacheSpec.from_model(
        full, slots=192, max_seq=2048, page_tokens=64, pages=4096,
        dtype=jnp.bfloat16).bytes == 8 * 4097 * 64 * 640 * 2 + 192 * 32 * 4
    # another model's spec is what it was
    other = kvcache.PagedCacheSpec.from_model(
        ModelConfig(name="transformer", n_layers=2, d_model=64, n_heads=4,
                    n_kv_heads=2), slots=2, max_seq=16, page_tokens=4)
    assert not other.latent and other.pools == 2


# ------------------------------------------------ the scheduler's part


def test_run_serve_carries_the_counts_on_its_spans(mesh):
    from tpudist.obs import trace as trace_lib
    from tpudist.serve import scheduler as sched
    cfg, _ = configs()
    params = init_params(cfg, mesh, seed=SEED)
    eng = PagedServeEngine(cfg, mesh, slots=2, max_seq=44, prompt_pad=16,
                           decode_k=4, page_tokens=PAGE, pages=20,
                           dtype=jnp.float32)
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
    for a in steps + fills:
        assert {"moe_pairs_local", "moe_pairs_per_expert",
                "moe_experts_hit", "moe_blocks", "moe_pairs_zero",
                "moe_pairs_all"} <= set(a)
        assert 0 <= a["moe_pairs_zero"] <= a["moe_pairs_all"]
    for a in steps:
        assert {"kv_full_pages", "active"} <= set(a)
        assert "kv_window_tokens" not in a
        # top-4 for each live slot, a layer a token step
        assert a["moe_pairs_all"] <= 4 * a["active"]
    assert 0.1 < summary["moe_zero_share"] < 0.6
    assert summary["moe_zero_share"] == pytest.approx(
        summary["moe_pairs_zero_mean"] / summary["moe_pairs_all_mean"],
        rel=1e-3)
    assert summary["kv_window_tokens_total"] == 0
    assert [s["args"] for s in spans if s["name"] == "experts_path"] \
        == [{"path": "loop", "prefill": "loop"}]
    assert 0 < summary["kv_pages_used_peak"] <= 20


def test_the_engine_and_the_config_refuse_what_is_not_built(mesh):
    cfg, _ = configs()
    assert get_model("longcatflash") is M
    make = lambda **kw: PagedServeEngine(
        cfg, mesh, slots=2, max_seq=44, prompt_pad=16, page_tokens=PAGE,
        dtype=jnp.float32, **kw)
    with pytest.raises(ValueError, match="speculate-k over a latent cache"):
        make(speculate_k=3)
    eng = make()
    with pytest.raises(ValueError, match="shared prefix over a latent"):
        eng.register_prefix(None, eng.init_state(), tokens(8), 8)
    # the model's own fields set for a model that does not declare them:
    # refused by the model layer (``models.model_for``), which the engine
    # and ``init_params`` ask; ``ModelConfig`` itself knows no model
    alien = ModelConfig(name="transformer", kv_lora_rank=16)
    with pytest.raises(ValueError, match="belong to the model"):
        model_for(alien)
    with pytest.raises(ValueError, match="kv_lora_rank.*longcatflash"):
        PagedServeEngine(alien, mesh, slots=2, max_seq=44, prompt_pad=16,
                         page_tokens=PAGE, dtype=jnp.float32)
    with pytest.raises(ValueError, match="belong to the model"):
        init_params(ModelConfig(name="cohere2moe", routed_scaling=6.0), mesh)
    # ... and this model's own widths left out
    with pytest.raises(ValueError, match="needs .*v_head_dim"):
        model_for(ModelConfig(
            name="longcatflash", q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, d_ff_dense=48))
    assert model_for(cfg) is M and model_for(ModelConfig()).__name__ \
        == "tpudist.models.mlp"


def test_a_model_claims_config_fields_by_declaring_them(monkeypatch):
    """The rule keys on what a module declares, not on a model's name: a
    second model that declares ``kv_lora_rank`` may set it, and still not
    the fields it does not declare."""
    import types

    from tpudist import models
    other = types.SimpleNamespace(CONFIG_FIELDS=("kv_lora_rank",))
    monkeypatch.setitem(models._REGISTRY, "latent2", other)
    assert model_for(ModelConfig(name="latent2", kv_lora_rank=16)) is other
    with pytest.raises(ValueError, match=r"\['n_zero_experts'\] belong"):
        model_for(ModelConfig(name="latent2", kv_lora_rank=16,
                              n_zero_experts=4))
    with pytest.raises(ValueError, match="belong to the model"):
        model_for(ModelConfig(name="sdarmoe", kv_lora_rank=16))


def test_the_cli_serves_the_model(tmp_path):
    from tpudist.serve import cli
    flags = ["--model", "longcatflash", "--n-layers", "2", "--d-model", "32",
             "--n-heads", "4", "--d-ff", "16", "--d-ff-dense", "48",
             "--n-experts", "16", "--n-experts-held", "4",
             "--n-zero-experts", "8", "--expert-top-k", "4",
             "--q-lora-rank", "24", "--kv-lora-rank", "16",
             "--qk-nope-head-dim", "8", "--qk-rope-head-dim", "4",
             "--v-head-dim", "8", "--kv-page-tokens", "4", "--max-seq", "48",
             "--prompt-pad", "16", "--requests", "6", "--max-new-tokens",
             "9", "--save-dir", str(tmp_path)]
    summary = cli.run(cli.parse_args(flags))
    assert summary["completed"] == 6 and summary["generated_tokens"] == 54
    assert (summary["prefill_compiles"], summary["decode_compiles"]) == (1, 1)
    assert 0.1 < summary["moe_zero_share"] < 0.6
    with pytest.raises(ValueError, match="latent cache"):
        cli.run(cli.parse_args(flags + ["--speculate-k", "4"]))
