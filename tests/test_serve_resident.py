"""The serve engine holds the weights at rest in its compute dtype.

``PagedServeEngine._resident`` converts a params tree once, on the device, and
the programs then take weights for which ``scopes.cast`` is a no-op. What
is held here:

* (a) the same bits: a bfloat16 engine fed float32 params serves tokens
  and logits BITWISE equal to one fed the same params cast beforehand;
* (b) identity: nothing to convert, nothing copied;
* (c) once per tree, with the ``weights_resident`` span as its counter;
* (d) the traced programs convert no weight (read from the jaxpr);
* (e) the resident tree dies with the engine (``make_room`` relies on it).
"""

import dataclasses
import gc
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend.core import Var

from tpudist.config import ModelConfig, ParallelConfig
from tpudist.obs import trace as trace_mod
from tpudist.parallel import build_mesh
from tpudist.serve import scheduler as sched
from tpudist.serve.engine import PagedServeEngine, init_params

BF16 = jnp.dtype(jnp.bfloat16)

TINY_TF = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      max_seq_len=32)
# 9 layers: the layer loop unrolls up to 8, only a deeper model runs the
# rolled scan that every real depth compiles to
DEEP_TF = dataclasses.replace(TINY_TF, n_layers=9, d_model=16, d_ff=32)
TINY_MOE = ModelConfig(name="moe", vocab_size=64, n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       max_seq_len=32, n_experts=4, expert_top_k=2,
                       capacity_factor=4.0)
TINY_C2M = ModelConfig(
    name="cohere2moe", vocab_size=64, n_layers=4, d_model=32, n_heads=8,
    n_kv_heads=2, head_dim=8, d_ff=16, n_experts=16, n_experts_held=4,
    expert_first=0, expert_top_k=4, n_shared_experts=2, sliding_window=6,
    rope_theta=50000.0, logit_scale=0.5)

# name -> (model config, engine keywords, shared prefix)
CASES = {
    "paged-transformer": (TINY_TF, dict(page_tokens=8, speculate_k=3), 8),
    "paged-moe": (TINY_MOE, dict(page_tokens=8), 0),
    "paged-cohere2moe": (TINY_C2M, dict(page_tokens=4, ring_margin=2), 0),
    # plain decode over one page a slot: no verify program, no prefix
    "transformer-one-page": (TINY_TF, dict(page_tokens=32), 0),
    "paged-transformer-l9": (DEEP_TF, dict(page_tokens=8, speculate_k=3),
                             0),
}
SERVED = [c for c in CASES if not c.endswith("l9")]


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(ParallelConfig(), devices=jax.devices()[:1])


def as_dtype(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def float32_params(cfg, mesh, seed=0):
    # cohere2moe makes its weights in bfloat16 in place: widened, so that
    # every case starts from a tree the engine has to convert
    return as_dtype(init_params(cfg, mesh, seed=seed), jnp.float32)


def engine_for(case, mesh, dtype=jnp.bfloat16, probe=False):
    cfg, kw, _ = CASES[case]
    cls = Probe if probe else PagedServeEngine
    return cls(cfg, mesh, slots=2, max_seq=32, prompt_pad=16, decode_k=4,
               dtype=dtype, **kw)


class Probe(PagedServeEngine):
    """The engine, with every logit it samples from handed to the host."""

    def _tied_logits(self, params, h):
        logits = super()._tied_logits(params, h)
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)),
                           logits, ordered=True)
        return logits


def serve(case, mesh, params):
    """Warm-up, then a whole ``run_serve`` (prefill, several decode
    dispatches; on the paged transformer ``register_prefix`` and
    ``verify`` too): the tokens of every request and every logit row the
    engine sampled from, in order."""
    cfg, _, prefix_len = CASES[case]
    eng = engine_for(case, mesh, probe=True)
    eng.seen = []
    eng.warmup(params)
    eng.seen.clear()
    reqs = sched.make_requests(4, prompt_pad=16, vocab_size=cfg.vocab_size,
                               max_new=10, rate=0.0, seed=3,
                               prefix_len=prefix_len)
    shared = (sched.shared_prefix_tokens(prefix_len, cfg.vocab_size, seed=3)
              if prefix_len else None)
    summary = sched.run_serve(eng, params, reqs, shared_prefix=shared)
    eng.assert_two_programs()
    assert summary["completed"] == 4, summary["partition"]
    assert summary["dispatches"] >= 2
    if prefix_len:
        assert summary["shared_prefix_len"] == prefix_len
        assert summary["verify_compiles"] == 1
    tokens = {rid: r["tokens"] for rid, r in summary["results"].items()}
    return tokens, eng.seen


def one_admission(eng, params):
    """A prompt of 5 into slot 0 and one decode dispatch behind it."""
    state = eng.init_state()
    assert eng.alloc.admit(0, 5) and eng.alloc.ensure(0, 5 + eng.decode_k)
    state, _ = eng.prefill(params, state, np.zeros(16, np.int32), 5, 0, 6)
    state, toks, _ = eng.decode(params, state)
    return state, toks


# ------------------------------------------------------- (a) the same bits


@pytest.mark.parametrize("case", SERVED)
def test_float32_params_serve_the_bits_of_params_cast_beforehand(mesh, case):
    cfg = CASES[case][0]
    params = float32_params(cfg, mesh)
    tokens, logits = serve(case, mesh, params)
    want_tokens, want_logits = serve(case, mesh, as_dtype(params, BF16))
    assert tokens == want_tokens
    assert len(logits) == len(want_logits) > 2
    for got, want in zip(logits, want_logits):
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------ (b) identity


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_a_tree_at_rest_in_the_engines_dtype_is_handed_back_itself(
        mesh, dtype):
    """bfloat16 over bfloat16, and the serve CLI's case: a float32 engine
    over ``init_params``' float32."""
    params = as_dtype(init_params(TINY_TF, mesh, seed=1), dtype)
    tracer = trace_mod.configure(enabled=True)
    try:
        eng = engine_for("paged-transformer", mesh, dtype=dtype)
        got = eng._resident(params)
        assert got is params
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
            assert a is b
        eng.warmup(params)
        assert eng._resident(params) is params
        assert not [e for e in tracer.events()
                    if e["name"] == "weights_resident"]
    finally:
        trace_mod.configure()


def test_only_floating_leaves_of_another_dtype_are_converted(devices8):
    """A mixed tree on a tensor-parallel mesh: the float32 leaves come
    back in bfloat16 under their own sharding, the others as the same
    arrays."""
    mesh = build_mesh(ParallelConfig(data=1, tensor=2),
                      devices=devices8[:2])
    params = init_params(TINY_TF, mesh, seed=2)
    params["embed"] = params["embed"].astype(BF16)
    params["steps"] = jnp.arange(3)
    eng = engine_for("paged-transformer", mesh)
    got = eng._resident(params)
    assert got is not params
    assert got["embed"] is params["embed"]
    assert got["steps"] is params["steps"]
    src = jax.tree.leaves(params["layers"]) + [params["final_norm"]]
    out = jax.tree.leaves(got["layers"]) + [got["final_norm"]]
    assert any(not w.sharding.is_fully_replicated for w in src)
    for w, c in zip(src, out):
        assert c.dtype == BF16 and c.shape == w.shape
        assert c.sharding.is_equivalent_to(w.sharding, w.ndim)
        np.testing.assert_array_equal(np.asarray(c),
                                      np.asarray(w.astype(BF16)))


# ------------------------------------------------------ (c) once per tree


def test_a_tree_is_converted_once_and_a_new_tree_again(mesh):
    tracer = trace_mod.configure(enabled=True)
    try:
        eng = engine_for("paged-transformer", mesh)
        params = init_params(TINY_TF, mesh, seed=1)
        spans = lambda: [e for e in tracer.events()
                         if e["name"] == "weights_resident"]
        eng.warmup(params)
        first = eng._resident(params)
        assert len(spans()) == 1
        state, toks = one_admission(eng, params)
        jax.block_until_ready(toks)
        assert eng._resident(params) is first
        assert len(spans()) == 1
        other = init_params(TINY_TF, mesh, seed=2)
        second = eng._resident(other)
        assert second is not first and len(spans()) == 2
        assert eng._resident(other) is second and len(spans()) == 2
    finally:
        trace_mod.configure()
    n = len(jax.tree.leaves(params))
    want_in = sum(w.nbytes for w in jax.tree.leaves(params))
    for e in spans():
        assert e["cat"] == "serve"
        assert e["args"] == {"leaves": n, "leaves_cast": n,
                             "bytes_in": want_in,
                             "bytes_out": want_in // 2}
    assert {w.dtype for w in jax.tree.leaves(second)} == {BF16}


# --------------------------------- (d) the programs convert no weight


# what a weight stays a weight through (not ``gather``: rows looked up in
# the embedding table are activations, and ``rmsnorm`` widens those)
_VIEWS = {"slice", "dynamic_slice", "squeeze", "reshape", "transpose",
          "broadcast_in_dim", "copy"}


def _inner(eqn):
    """``(sub-jaxpr, the equation's operands it takes as invars)`` for
    every jaxpr an equation carries."""
    p, name = eqn.params, eqn.primitive.name
    if name == "cond":
        return [(b.jaxpr, eqn.invars[1:]) for b in p["branches"]]
    if name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        return [(p["cond_jaxpr"].jaxpr,
                 eqn.invars[:nc] + eqn.invars[nc + nb:]),
                (p["body_jaxpr"].jaxpr, eqn.invars[nc:])]
    out = []
    for v in p.values():
        j = getattr(v, "jaxpr", v)
        # a call's or a scan's jaxpr takes the operands one for one; a
        # scatter's or a reduction's combiner takes scalars, no operand
        if hasattr(j, "eqns") and len(j.invars) == len(eqn.invars):
            out.append((j, eqn.invars))
    return out


def weight_converts(jaxpr, weights):
    """Every ``convert_element_type`` to another dtype whose operand is
    one of ``weights`` (variables of ``jaxpr``) or a slice, reshape or
    transpose of one, through every loop, branch and call."""
    weights, found = set(weights), []
    is_weight = lambda v: isinstance(v, Var) and v in weights
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if eqn.invars and is_weight(eqn.invars[0]):
            if name in _VIEWS:
                weights.update(eqn.outvars)
            elif name == "convert_element_type" and (
                    eqn.params["new_dtype"] != eqn.invars[0].aval.dtype):
                found.append(eqn)
        for sub, operands in _inner(eqn):
            found += weight_converts(
                sub, [sv for sv, v in zip(sub.invars, operands)
                      if is_weight(v)])
    return found


def programs(eng, params):
    """{name: the program's jaxpr}, params first among its arguments."""
    state = eng.init_state()
    tokens = jnp.zeros((1, eng.prompt_pad), jnp.int32)
    one = jnp.int32(1)
    with jax.set_mesh(eng.mesh):
        table = jnp.asarray(eng.alloc.table, jnp.int32)
        da = jnp.ones((eng.slots,), bool)
        out = {
            "prefill": jax.make_jaxpr(eng._paged_prefill_body)(
                params, state, tokens, one, one, one, table[0], one),
            "decode": jax.make_jaxpr(
                eng._paged_decode_body, static_argnums=(2,))(
                    params, state, eng.decode_k, table, da)}
        if eng.speculate_k:
            draft = jnp.zeros((eng.slots, eng.speculate_k - 1), jnp.int32)
            out["verify"] = jax.make_jaxpr(eng._paged_verify_body)(
                params, state, draft, table, da)
        return out


@pytest.mark.parametrize("case", list(CASES))
def test_the_programs_convert_no_weight(mesh, case):
    cfg = CASES[case][0]
    params = float32_params(cfg, mesh)
    eng = engine_for(case, mesh)
    n = len(jax.tree.leaves(params))
    seen = {}
    for fed, tree in (("as handed in", params),
                      ("resident", eng._resident(params))):
        for name, closed in programs(eng, tree).items():
            seen[fed, name] = weight_converts(closed.jaxpr,
                                              closed.jaxpr.invars[:n])
    for (fed, name), found in seen.items():
        if fed == "resident":
            assert not found, (name, found)
        else:
            # the walk does see them where they are: the float32 tree,
            # not taken through the engine's helper, is converted at use
            assert len(found) >= 3, (name, found)
    assert {name for _, name in seen} >= {"prefill", "decode"}


# ------------------------------------------- (e) it dies with the engine


def make_room():
    """``perfbench/lib/reference.make_room``, word for word."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def test_the_resident_tree_dies_with_the_engine(mesh):
    params = init_params(TINY_TF, mesh, seed=4)
    make_room()       # what earlier tests' programs still hold goes first
    live_bf16 = lambda: sum(1 for a in jax.live_arrays() if a.dtype == BF16)
    before = live_bf16()
    eng = engine_for("paged-transformer", mesh)
    eng.warmup(params)
    state, toks = one_admission(eng, params)
    jax.block_until_ready(toks)
    resident = [weakref.ref(w)
                for w in jax.tree.leaves(eng._resident(params))]
    assert len(resident) == len(jax.tree.leaves(params))
    assert all(r().dtype == BF16 for r in resident)
    assert live_bf16() >= before + len(resident)
    del eng, state, toks
    make_room()
    assert all(r() is None for r in resident)
    assert live_bf16() == before
    assert all(w.dtype == jnp.float32 for w in jax.tree.leaves(params))
