"""The ``sdarmoe`` model against its plain reference
(``perfbench/lib/reference_sdar.py``: float32 at ``highest``, no cache, no
grouped products, an arbitrary visibility mask, weights drawn there from
the seed) at tiny widths on the CPU: the block-causal forward, prefill then
block denoising through ``PagedServeEngine`` over the paged pool, step by
step against the reference's ``[clean; noisy]`` forwards, the commit, the
budget, the dropless experts under skew, the planted faults, the spans.

The tolerance: program and reference compute the same float32 mathematics
in another order (grouped against per-expert products, a paged read
against a masked whole), which moved logits of size ~3 by at most 4e-6 in
every case below. ``TOL`` leaves that five times of room; the same program
in bfloat16 misses it by three orders (``test_bfloat16_is_outside``), and
the float8 control and every planted fault by more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import reference_sdar as ref
from tpudist.config import ModelConfig, ParallelConfig
from tpudist.models import cohere2moe, dropless
from tpudist.models import sdarmoe as M
from tpudist.models import transformer as T
from tpudist.obs import trace as trace_lib
from tpudist.parallel.mesh import build_mesh
from tpudist.serve import scheduler as sched
from tpudist.serve.engine import PagedServeEngine

jax.config.update("jax_default_matmul_precision", "highest")

TOL = 2e-5
SEED = 5
B, MASK, PAGE, MAX_SEQ, PAD = 4, 63, 8, 32, 16


def configs(layers=2, experts=8):
    """The same tiny model as the program's config and the reference's."""
    file = {"hidden_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 8,
            "moe_intermediate_size": 16, "num_experts": experts,
            "num_experts_per_tok": 2, "vocab_size": 64,
            "num_hidden_layers": layers, "rope_theta": 1000000,
            "rms_norm_eps": 1e-6, "block_length": B, "mask_token_id": MASK}
    cfg = ModelConfig(
        name="sdarmoe", vocab_size=64, n_layers=layers, d_model=32,
        n_heads=4, n_kv_heads=2, head_dim=8, d_ff=16, n_experts=experts,
        expert_top_k=2, rope_theta=1e6, norm_eps=1e-6, block_length=B,
        denoise_steps=B, mask_token_id=MASK)
    return cfg, file


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, (n,)).astype(
        np.int32)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(ParallelConfig(), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def params():
    return f32(M.init(jax.random.PRNGKey(SEED), configs()[0]))


def engine_of(mesh, dtype=jnp.float32, slots=2, **kw):
    return PagedServeEngine(configs()[0], mesh, slots=slots, max_seq=MAX_SEQ,
                            prompt_pad=PAD, page_tokens=PAGE, dtype=dtype,
                            **kw)


def serve_one(eng, params, prompt, max_new, slot=1):
    """One request through the engine's own calls. -> (the request as the
    reference takes it, the final state, the slot's page row)."""
    state, alloc = eng.init_state(), eng.new_allocator()
    padded = np.zeros(PAD, np.int32)
    padded[:len(prompt)] = prompt
    assert alloc.admit(slot, len(prompt))
    state, blocks = eng.prefill(params, state, padded, len(prompt), slot,
                                max_new)
    assert int(blocks) == len(prompt) // B
    served, steps, surplus = [], [], []
    while len(served) < max_new:
        assert alloc.ensure(
            slot, (len(prompt) + len(served)) // B * B + B - 1)
        state, toks, valid = eng.decode(params, state)
        toks, valid = np.asarray(toks)[:, slot], np.asarray(valid)[:, slot]
        at = eng.read_block(state)[:, slot]
        served += [int(t) for t in toks[valid]]
        steps += [int(a) for a in at[valid]]
        surplus = [(int(w), int(toks[w]), int(at[w]))
                   for w in np.flatnonzero(~valid & (at >= 0))]
    assert not np.asarray(state.active)[slot]
    req = ref.request_of(prompt, served, steps, surplus, B)
    return req, state, alloc.row(slot)


def block_forward(params, cfg, pool_k, pool_v, row, block_tok, start,
                  dtype=jnp.float32):
    """The model's forward of ONE block alone, the tokens ``block_tok`` at
    positions start.. of the slot whose page row is ``row``: the block's
    own keys written by this forward, nothing later visible.
    -> (logits (B, V), pool_k, pool_v)."""
    pos = (start + np.arange(B))[None].astype(np.int32)
    h, pk, pv, _ = M.paged_hidden_states(
        params, jnp.asarray(block_tok, jnp.int32)[None], cfg, dtype=dtype,
        pool_k=jnp.asarray(pool_k), pool_v=jnp.asarray(pool_v),
        page_table=jnp.asarray(row)[None], positions=jnp.asarray(pos),
        write_ok=jnp.ones((1, B), bool),
        see=jnp.full((1, B), start + B - 1, jnp.int32), page_tokens=PAGE)
    return M.head_logits(params, h, dtype)[0], pk, pv


def replay(params, cfg, state, row, block_tok, start, dtype=jnp.float32):
    """``block_forward`` against the pool as the run left it (everything
    before the block committed)."""
    return block_forward(params, cfg, state.pool_k, state.pool_v, row,
                         block_tok, start, dtype)


def two_forward_dispatch(params, cfg, pool_k, pool_v, row, block_tok,
                         block_open, start):
    """The order a dispatch ran before the commit rode with the next
    block: ``B`` denoising forwards of the block alone, one position
    unmasked a step, then the commit forward over the final tokens.
    -> (final tokens, the step each position was unmasked at, the pool
    before the commit, the pool after it)."""
    tok = np.where(block_open, MASK, block_tok)
    still, at = np.array(block_open), np.full(B, -1)
    for s in range(B):
        logits, pool_k, pool_v = block_forward(params, cfg, pool_k, pool_v,
                                               row, tok, start)
        conf = np.asarray(jnp.exp(logits.max(axis=-1)
                                  - jax.nn.logsumexp(logits, axis=-1)))
        if still.any():
            pick = int(np.argmax(np.where(still, conf, -1.0)))
            tok[pick] = int(np.argmax(np.asarray(logits[pick])))
            still[pick], at[pick] = False, s
    _, ck, cv = block_forward(params, cfg, pool_k, pool_v, row, tok, start)
    return tok, at, (pool_k, pool_v), (ck, cv)


def pages_but_trash(pool):
    return np.asarray(pool)[:, :, :-1]


def reference_steps(file, req, pad_to=MAX_SEQ):
    """[step] -> (pad_to, V): the reference's logits on the noisy rows."""
    mask = ref.denoise_mask(pad_to, B)
    rows = [ref.denoise_rows(req, s, file, pad_to) + (mask,)
            for s in range(B)]
    hs = ref.hiddens(SEED, file, [rows])[0]
    head = ref.head_weights(SEED, file)
    return [np.asarray(ref._mm(h[pad_to:], head, None)) for h in hs]


# ------------------------------------------------- (a) the full forward


@pytest.mark.parametrize("layers", [2, 4])
def test_forward_logits_match_the_reference_under_the_block_mask(layers):
    cfg, file = configs(layers)
    p = f32(M.init(jax.random.PRNGKey(SEED), cfg))
    t = tokens(23)
    got = M.apply(p, jnp.asarray(t)[None], cfg, dtype=jnp.float32)[0]
    want = ref.logits(SEED, file, t)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < TOL
    # the mask is the model's, not the causal one: position 0 sees 1..3
    causal = ref.logits(SEED, file, t, mask=jnp.tril(
        jnp.ones((23, 23), bool)))
    assert float(jnp.abs(causal[0] - want[0]).max()) > 100 * TOL


# ------------------------- (b) prefill, then blocks through the paged pool


@pytest.mark.parametrize("plen,max_new", [(8, 8), (9, 7), (11, 5), (3, 6),
                                          (8, 6)],
                         ids=["rem0", "rem1", "rem3", "no_whole_block",
                              "budget_cut"])
def test_denoising_matches_the_reference_step_by_step(mesh, params, plen,
                                                      max_new):
    cfg, file = configs()
    eng = engine_of(mesh)
    prompt = tokens(plen, seed=plen)
    req, state, row = serve_one(eng, params, prompt, max_new)
    n = len(req["tokens"])
    assert n % B == 0 and req["served"].sum() == max_new
    # the budget is honoured to the token: the surplus was computed (it has
    # a step) and not served
    assert n - plen - max_new == (-(plen + max_new)) % B
    want = reference_steps(file, req)
    for start in range(plen // B * B, n, B):
        at = np.arange(start, start + B)
        for s in range(B):
            noisy = np.where(req["step"][at] >= s, MASK, req["tokens"][at])
            got, _, _ = replay(params, cfg, state, row, noisy, start)
            assert np.abs(np.asarray(got) - want[s][at]).max() < TOL
            masked = at[req["step"][at] >= s]
            if not len(masked):
                continue
            # the order of unmasking: the reference's most confident
            # masked position is the one the program unmasked at step s,
            # with the reference's first choice of token
            p = jax.nn.softmax(want[s][masked], axis=-1)
            chosen = masked[int(np.argmax(np.asarray(p.max(axis=-1))))]
            assert req["step"][chosen] == s
            assert req["tokens"][chosen] == int(np.argmax(want[s][chosen]))
    eng.assert_two_programs()


def test_first_block_takes_the_prompts_remainder_as_given(mesh, params):
    eng = engine_of(mesh)
    prompt = tokens(10, seed=3)
    req, _, _ = serve_one(eng, params, prompt, 6)
    assert list(req["step"][:10]) == [-1] * 10
    assert sorted(req["step"][10:12]) == [0, 1]         # two were masked
    assert sorted(req["step"][12:16]) == [0, 1, 2, 3]


def test_the_cache_keeps_the_final_tokens_k_and_v(mesh, params):
    """The commit is not skipped: after a block, the pool holds at its
    positions the K and V of a forward over the block's FINAL tokens, not
    the last denoising step's (which saw the mask token at the position
    it was about to unmask). The commit of a request's LAST block would
    ride in a dispatch that never comes: that block keeps the last step's
    K and V, which nothing reads again."""
    cfg, _ = configs()
    eng = engine_of(mesh)
    req, state, row = serve_one(eng, params, tokens(8, seed=1), 12)
    for start, committed in ((8, True), (12, True), (16, False)):
        at = np.arange(start, start + B)
        page, off = row[start // PAGE], start % PAGE
        final = replay(params, cfg, state, row, req["tokens"][at], start)
        last = np.where(req["step"][at] == B - 1, MASK, req["tokens"][at])
        step = replay(params, cfg, state, row, last, start)
        kept, other = (final, step) if committed else (step, final)
        for got, want in ((state.pool_k, kept[1]), (state.pool_v, kept[2])):
            assert np.abs(np.asarray(got[:, :, page, off:off + B])
                          - np.asarray(want[:, :, page, off:off + B])
                          ).max() < TOL
        assert np.abs(np.asarray(state.pool_k[:, :, page, off:off + B])
                      - np.asarray(other[1][:, :, page, off:off + B])
                      ).max() > 1e-3


def admit(eng, params, state, alloc, slot, prompt, max_new):
    padded = np.zeros(PAD, np.int32)
    padded[:len(prompt)] = prompt
    assert alloc.admit(slot, len(prompt))
    return eng.prefill(params, state, padded, len(prompt), slot, max_new)[0]


def dispatch(eng, params, state, alloc, starts):
    """One dispatch of the slots ``starts`` names, each at its block's
    first position. -> (state, tokens (B, slots), valid, stats)."""
    for slot, start in starts.items():
        assert alloc.ensure(slot, start + B - 1)
    inside = np.zeros(eng.slots, bool)
    inside[list(starts)] = True
    state, toks, valid = eng.decode(params, state, dispatch_active=inside)
    return state, np.asarray(toks), np.asarray(valid), eng.read_stats(state)


def test_a_dispatch_leaves_the_pool_the_two_forward_order_leaves(mesh,
                                                                  params):
    """Slot 0's second block beside its first block's commit, slot 1's first
    block (no commit) in the same dispatch: the tokens, the order of
    unmasking and every page are what the order before the fusion gives,
    a block's denoising forwards then its commit forward, block by block."""
    cfg, _ = configs()
    eng = engine_of(mesh)
    state, alloc = eng.init_state(), eng.new_allocator()
    state = admit(eng, params, state, alloc, 0, tokens(8, seed=4), 12)
    state = admit(eng, params, state, alloc, 1, tokens(9, seed=6), 12)
    pool = (np.asarray(state.pool_k), np.asarray(state.pool_v))
    given = np.asarray(state.block_tok), np.asarray(state.block_open)
    # slot 1 waits outside the first dispatch
    state, toks, _, stats = dispatch(eng, params, state, alloc, {0: 8})
    assert (stats["commits_fused"], stats["forwards_launched"]) == (0, B)
    tok, at, _, pool = two_forward_dispatch(params, cfg, *pool, alloc.row(0),
                                            given[0][0], given[1][0], 8)
    np.testing.assert_array_equal(toks[:, 0], tok)
    np.testing.assert_array_equal(eng.read_block(state)[:, 0], at)
    assert list(np.asarray(state.commit_due)) == [True, False]

    block = np.asarray(state.block_tok), np.asarray(state.block_open)
    state, toks, _, stats = dispatch(eng, params, state, alloc,
                                     {0: 12, 1: 8})
    assert (stats["commits_fused"], stats["forwards_launched"]) == (1, B)
    for slot, start in ((0, 12), (1, 8)):
        tok, at, pool, _ = two_forward_dispatch(
            params, cfg, *pool, alloc.row(slot), block[0][slot],
            block[1][slot], start)
        np.testing.assert_array_equal(toks[:, slot], tok)
        np.testing.assert_array_equal(eng.read_block(state)[:, slot], at)
    # the same float32 mathematics over rows of another batch shape: ~1e-6
    # apart on entries of size ~4
    for got, want in zip((state.pool_k, state.pool_v), pool):
        assert np.abs(pages_but_trash(got) - pages_but_trash(want)).max() \
            < TOL
    assert list(np.asarray(state.commit_due)) == [True, True]


@pytest.mark.parametrize("left", ["never_used", "finished", "evicted"])
def test_a_slots_next_request_carries_no_commit(mesh, params, left):
    """A slot in its first block carries no commit, whoever held it: a
    request that finished dropped its commit in the program that finished
    it; one the host evicted mid-request still had its commit due, and the
    next admission's prefill clears it. The next request's first dispatch
    writes nothing outside its own block, so no stale commit lands in pages
    that request or any other now owns."""
    eng = engine_of(mesh)
    state, alloc = eng.init_state(), eng.new_allocator()
    slot = 1
    if left != "never_used":
        state = admit(eng, params, state, alloc, slot, tokens(8, seed=7),
                      4 if left == "finished" else 12)
        state, _, _, _ = dispatch(eng, params, state, alloc, {slot: 8})
        live = left == "evicted"
        assert bool(np.asarray(state.active)[slot]) == live
        assert bool(np.asarray(state.commit_due)[slot]) == live
        alloc.free_slot(slot)
    state = admit(eng, params, state, alloc, slot, tokens(10, seed=8), 6)
    assert not np.asarray(state.commit_due)[slot]
    before = (np.asarray(state.pool_k), np.asarray(state.pool_v))
    state, _, valid, stats = dispatch(eng, params, state, alloc, {slot: 8})
    assert stats["commits_fused"] == 0 and valid[:, slot].sum() == 2
    page = alloc.row(slot)[8 // PAGE]
    off = 8 % PAGE
    for was, now in zip(before, (state.pool_k, state.pool_v)):
        now = np.array(now)
        # the block's own provisional k/v, and nothing else
        now[:, :, page, off:off + B] = was[:, :, page, off:off + B]
        np.testing.assert_array_equal(pages_but_trash(now),
                                      pages_but_trash(was))


def test_bfloat16_is_outside(mesh, params):
    cfg, file = configs()
    eng = engine_of(mesh)
    req, state, row = serve_one(eng, params, tokens(8, seed=2), 8)
    want = reference_steps(file, req)
    at = np.arange(8, 12)
    noisy = np.where(req["step"][at] >= 0, MASK, req["tokens"][at])
    cast = lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)
    got, _, _ = replay(cast(params), cfg, state._replace(
        pool_k=state.pool_k.astype(jnp.bfloat16),
        pool_v=state.pool_v.astype(jnp.bfloat16)), row, noisy, 8,
        dtype=jnp.bfloat16)
    assert np.abs(np.asarray(got) - want[0][at]).max() > 100 * TOL


# ------------------------------------------------ (c) run_serve, the faults


@pytest.fixture(scope="module")
def served(mesh, params):
    """Requests of every remainder through ``run_serve``, scored by the
    reference with the control and every planted fault beside it."""
    _, file = configs()
    eng = engine_of(mesh, slots=3)
    # the tracer first: the engine says its ``experts_path`` at the first
    # dispatch a tracer sees, which is the warm-up's
    tracer = trace_lib.configure(enabled=True)
    eng.warmup(params)
    lens = [(8, 9), (9, 12), (6, 7), (11, 5), (5, 10), (12, 8)]
    reqs = []
    for i, (pl, mn) in enumerate(lens):
        t = np.zeros(PAD, np.int32)
        t[:pl] = tokens(pl, seed=10 + i)
        reqs.append(sched.Request(rid=i, arrival_s=0.0, tokens=t,
                                  prompt_len=pl, max_new=mn))
    logged = []

    class Log:
        def log(self, **kw):
            logged.append(kw)

        def flush(self):
            pass
    summary = sched.run_serve(eng, params, reqs, metrics=Log())
    spans = tracer.events()
    trace_lib.configure(enabled=False)
    sample = [ref.request_of(r.tokens[:r.prompt_len], res["tokens"],
                             res["unmask_step"], res["surplus"], B)
              for r in reqs for res in [summary["results"][r.rid]]]
    variants = [("fp8", None)] + [(None, f) for f in ref.FAULTS
                                  + ref.ORDER_FAULTS]
    return summary, spans, logged, reqs, ref.served_gaps(
        SEED, file, sample, 256, variants)


def test_run_serve_serves_every_budget_to_the_token(served):
    summary, _, logged, reqs, got = served
    assert summary["completed"] == len(reqs) and summary["truncated"] == 0
    for r in reqs:
        res = summary["results"][r.rid]
        assert res["generated"] == len(res["tokens"]) == r.max_new
        assert len(res["unmask_step"]) == r.max_new
        assert (r.prompt_len + r.max_new + len(res["surplus"])) % B == 0
    assert summary["generated_tokens"] == sum(r.max_new for r in reqs)
    assert summary["block_length"] == B
    # a request's blocks each take B forwards a slot, and each but its last
    # one more: its commit, in the slot's next dispatch
    blocks = [-(-(r.prompt_len % B + r.max_new) // B) for r in reqs]
    assert summary["commits_fused"] == sum(n - 1 for n in blocks)
    assert summary["forwards_launched"] == B * summary["dispatches"]
    assert summary["forwards_per_token"] == round(
        sum((B + 1) * n - 1 for n in blocks) / summary["generated_tokens"], 4)
    assert summary["prefill_compiles"] == summary["decode_compiles"] == 1
    # the time to first token is taken at the first block's return
    first = [e for e in logged if e.get("kind") == "serve_first_tokens"]
    assert sorted(e["rid"] for e in first) == [r.rid for r in reqs]
    assert all(e["ttft_s"] > 0 and e["tokens"] >= 1 for e in first)
    # in float32 the program serves the reference's own first choices in
    # the reference's own order
    assert len(got["gaps"]) == summary["generated_tokens"]
    assert float(got["gaps"].max()) == 0.0
    assert float(got["conf_gaps"].max()) == 0.0


@pytest.mark.parametrize("name", ["fp8"] + list(ref.FAULTS))
def test_control_and_planted_faults_are_outside(served, name):
    got = served[-1]
    assert float(got[name + "_gaps"].mean()) > 1000 * TOL, name


@pytest.mark.parametrize("name", ref.ORDER_FAULTS)
def test_a_planted_order_of_unmasking_is_outside(served, name):
    """Another ORDER of unmasking serves the reference's own first choices
    (no logit gap can see it): the confidence gap has to, and does."""
    got = served[-1]
    assert float(got[name + "_gaps"].max()) == 0.0
    # confidences here are ~0.05: left to right reads 0.0085, the least
    # confident first more, the program itself exactly 0
    assert float(got[name + "_conf_gaps"].mean()) > 100 * TOL, name


def test_run_serve_carries_the_span_arguments(served):
    _, spans, _, reqs, _ = served
    steps = [s for s in spans if s["name"] == "decode_step"]
    assert steps
    for s in steps:
        a = s["args"]
        # a slot's B denoising forwards, and its previous block's commit
        # where one was due
        assert a["forwards"] == B * a["blocks"] + a["commits_fused"]
        assert a["blocks"] == a["active"] >= a["commits_fused"]
        assert a["forwards_launched"] == B
        assert 0 < a["tokens_emitted"] <= B * a["blocks"]
        assert a["moe_pairs_per_expert"] > 0 and a["moe_experts_hit"] > 0
        # blocks of rows the expert routine ran, a layer a forward: one an
        # expert hit at least
        assert a["moe_blocks"] >= a["moe_experts_hit"]
        assert a["kv_full_pages"] > 0
    assert sum(s["args"]["tokens_emitted"] for s in steps) \
        == sum(r.max_new for r in reqs)
    # every block but a request's last was committed once, and a slot's
    # first dispatch carried none
    assert sum(s["args"]["commits_fused"] for s in steps) \
        == sum(-(-(r.prompt_len % B + r.max_new) // B) - 1 for r in reqs)
    assert steps[0]["args"]["commits_fused"] == 0
    pre = [s for s in spans if s["name"] == "prefill"]
    assert sorted(s["args"]["blocks_written"] for s in pre) \
        == sorted(r.prompt_len // B for r in reqs)
    names = {s["name"] for s in spans}
    assert {"decode_enqueue", "decode_fence", "first_tokens"} <= names
    # said once, at the first traced dispatch: off the TPU the loop
    assert [s["args"] for s in spans if s["name"] == "experts_path"] \
        == [{"path": "loop", "prefill": "loop"}]
    summary = served[0]
    assert summary["moe_blocks_mean"] >= summary["moe_experts_hit_mean"] > 0


def test_the_experts_rest_as_one_stack_a_leaf_of_the_same_draws():
    """``init`` lays a layer's experts as three stacks; member ``i`` is
    the draw from ``fold_in(leaf_key, i)`` that the array-an-expert layout
    made, so the benchmark's reference (which makes its own weights from
    the seed) still agrees, and ``lp["e_gate"][i]`` reads as it did."""
    cfg, _ = configs()
    d, dff, E, L = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_layers
    key = jax.random.PRNGKey(SEED)
    params = M.init(key, cfg)
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) == 3 + 12 * L          # 3 x E x L arrays no more
    for l in (0, L - 1):
        lp = params["layers"][l]
        ks = dict(zip(M._LEAVES, jax.random.split(
            jax.random.fold_in(key, 1 + l), len(M._LEAVES))))
        for name, shape, fan in (("e_gate", (d, dff), d),
                                 ("e_up", (d, dff), d),
                                 ("e_down", (dff, d), dff)):
            assert lp[name].shape == (E,) + shape
            assert lp[name].dtype == jnp.bfloat16
            for i in (0, 1, E - 1):
                one = (jax.random.normal(jax.random.fold_in(ks[name], i),
                                         shape, jnp.float32)
                       / jnp.sqrt(fan)).astype(jnp.bfloat16)
                np.testing.assert_array_equal(
                    np.asarray(lp[name][i], np.float32),
                    np.asarray(one, np.float32))


def test_the_engine_refuses_what_is_not_built(mesh):
    cfg, _ = configs()
    with pytest.raises(ValueError, match="speculate-k"):
        engine_of(mesh, speculate_k=4)
    with pytest.raises(ValueError, match="denoise_steps"):
        PagedServeEngine(cfg.__class__(**{**cfg.__dict__,
                                          "denoise_steps": 2}), mesh,
                         slots=2, max_seq=MAX_SEQ, prompt_pad=PAD,
                         page_tokens=PAGE)
    # block lengths other than 4 are refused in words, not run unheld
    for b in (2, 8):
        with pytest.raises(ValueError, match=f"block_length {b} is not "
                                             f"built"):
            PagedServeEngine(cfg.__class__(**{**cfg.__dict__,
                                              "block_length": b,
                                              "denoise_steps": 0}), mesh,
                             slots=2, max_seq=MAX_SEQ, prompt_pad=PAD,
                             page_tokens=PAGE)
    with pytest.raises(ValueError, match="whole number of blocks"):
        PagedServeEngine(cfg, mesh, slots=2, max_seq=MAX_SEQ + 2,
                         prompt_pad=PAD, page_tokens=PAGE)
    with pytest.raises(ValueError, match="block_length"):
        PagedServeEngine(cfg.__class__(**{**cfg.__dict__,
                                          "block_length": 0}), mesh,
                         slots=2, max_seq=MAX_SEQ, prompt_pad=PAD,
                         page_tokens=PAGE)


def test_the_cli_serves_the_model_by_blocks_of_four(tmp_path):
    """``python -m tpudist.serve --model sdarmoe``: ``run_serve`` over the
    one engine, the block length the family's 4 and no flag to move it."""
    from tpudist.serve import cli
    flags = ["--model", "sdarmoe", "--n-layers", "2", "--d-model", "32",
             "--n-heads", "4", "--n-kv-heads", "2", "--head-dim", "8",
             "--d-ff", "16", "--n-experts", "8", "--expert-top-k", "2",
             "--kv-page-tokens", "4", "--max-seq", "48", "--prompt-pad",
             "16", "--requests", "4", "--max-new-tokens", "11", "--trace",
             "off", "--save-dir", str(tmp_path)]
    summary = cli.run(cli.parse_args(flags))
    assert summary["completed"] == 4 and summary["truncated"] == 0
    assert summary["generated_tokens"] == 4 * 11
    assert summary["block_length"] == 4
    assert summary["forwards_per_token"] >= 5 / 4
    assert summary["prefill_compiles"] == summary["decode_compiles"] == 1
    with pytest.raises(ValueError, match="speculate-k"):
        cli.run(cli.parse_args(flags + ["--speculate-k", "4"]))


@pytest.mark.parametrize("flag", ["--block-length", "--mask-token-id"])
def test_the_cli_has_no_knob_for_the_block(flag, capsys):
    from tpudist.serve import cli
    with pytest.raises(SystemExit) as e:
        cli.parse_args(["--model", "sdarmoe", flag, "8"])
    assert e.value.code == 2
    assert flag in capsys.readouterr().err


# -------------------------------------------- (d) the shared dropless routine


def dense_routed(y, top_e, top_w, experts, first, held):
    out = jnp.zeros(y.shape, jnp.float32)
    for i in range(held):
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        g = y @ experts[0][i]
        out = out + ((jax.nn.silu(g) * (y @ experts[1][i]))
                     @ experts[2][i]) * w[:, None]
    return out


def test_no_pair_is_dropped_under_a_skewed_router():
    """Every token's first choice is expert 2: one expert takes 40 pairs,
    more than two blocks of the block size a forward of 40 routes."""
    cfg, _ = configs()
    n, d, dff, E = 40, cfg.d_model, cfg.d_ff, cfg.n_experts
    assert dropless.block_rows(n, 2, E) == 32 < n
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ex = tuple(tuple(jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
                     for _ in range(E))
               for s in ((d, dff), (d, dff), (dff, d)))
    top_e = jnp.stack([jnp.full((n,), 2), jnp.asarray(
        rng.integers(3, E, (n,)))], axis=1).astype(jnp.int32)
    top_w = jnp.asarray(rng.uniform(0.2, 0.8, (n, 2)), jnp.float32)
    got, stats = jax.jit(lambda y, e, w: dropless.routed(
        y, e, w, ex, first=0, held=E, n_routed=E))(y, top_e, top_w)
    assert int(stats[0]) == 2 * n            # every pair on a held expert
    want = dense_routed(y, top_e, top_w, ex, 0, E)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("held,routed,k,n", [(16, 128, 8, 32),
                                             (128, 128, 8, 64)],
                         ids=["16_of_128_held", "all_128_held"])
def test_both_models_expert_layers_run_through_the_one_routine(held, routed,
                                                               k, n):
    """``cohere2moe``'s layer (sigmoid router, a share of the experts) and
    ``sdarmoe``'s (softmax router, every expert) through
    ``dropless.routed``, against the dense sum over the held experts."""
    d, dff = 16, 8
    rng = np.random.default_rng(held)
    y = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    lp = {"w_router": jnp.asarray(rng.normal(size=(d, routed)) / 4,
                                  jnp.float32)}
    for name, shape in (("e_gate", (d, dff)), ("e_up", (d, dff)),
                        ("e_down", (dff, d))):
        lp[name] = tuple(jnp.asarray(rng.normal(size=shape) / 3, jnp.float32)
                         for _ in range(held))
    ex = (lp["e_gate"], lp["e_up"], lp["e_down"])
    c_cfg = ModelConfig(name="cohere2moe", d_model=d, d_ff=dff,
                        n_experts=routed, n_experts_held=held,
                        expert_top_k=k)
    s_cfg = ModelConfig(name="sdarmoe", d_model=d, d_ff=dff,
                        n_experts=routed, expert_top_k=k, block_length=B)
    assert cohere2moe._routed.__globals__["dropless"] is dropless
    assert M._mix.__globals__["dropless"] is dropless
    # cohere2moe: its own router, the shared routine over its share
    e, w = cohere2moe._route(y, lp, c_cfg)
    got, stats = cohere2moe._routed(y, e, w, lp, c_cfg)
    assert float(jnp.abs(got - dense_routed(y, e, w, ex, 0, held)).max()) \
        < TOL
    assert int(stats[0]) == int(((e >= 0) & (e < held)).sum())
    if held == routed:
        # sdarmoe: its own router, every pair lands on a held expert
        e, w = M._route(y, lp, s_cfg)
        got, stats = M._mix(y[None], lp, s_cfg)
        want = dense_routed(y, e, w, ex, 0, held)
        assert float(jnp.abs(got[0] - want).max()) < TOL
        assert int(stats[0]) == n * k
        assert float(jnp.abs(jnp.sum(w, axis=-1) - 1).max()) < 1e-6


def test_block_rows_follow_what_a_forward_routes():
    # the accepted cell's programs keep the blocks they had
    assert dropless.block_rows(32, 8, 128) == 32
    assert dropless.block_rows(8192, 8, 128) == 512
    # 128 slots' blocks of 4 at 8 of 128: 32 pairs an expert, twice that
    assert dropless.block_rows(512, 8, 128) == 64
    assert dropless.block_rows(1024, 8, 128) == 128
    assert dropless.block_rows(8, 2, 8) == 16


def test_half_split_rope_is_the_transformers_convention():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 4, 8)),
                    jnp.float32)
    pos = jnp.asarray([[5, 6, 7], [0, 9, 30]], jnp.int32)
    assert float(jnp.abs(M.rope_half(x, pos, 1e6)
                         - T.window_rope(x, pos, 1e6)).max()) < 1e-6
