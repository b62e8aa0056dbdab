"""Pipeline parallelism vs the dense path on the virtual 8-device mesh.

The GPipe slot schedule, masked ring ends, and ppermute-transposed
backward must reproduce the dense transformer's loss and its training
trajectory exactly (same math, different schedule) — these tests pin that
in f32 where the comparison is tight.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import data, engine
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig)
from tpudist.parallel import build_mesh
from tpudist.parallel.pipeline import make_pp_loss_fn

MODEL = ModelConfig(name="transformer", vocab_size=128, n_layers=4,
                    d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                    max_seq_len=16)


def _cfg(batch=8, **par):
    return TrainConfig(batch_size=batch, lr=1e-2, seed=0, dtype="float32",
                       data=DataConfig(n_samples=batch),
                       model=MODEL, parallel=ParallelConfig(**par))


def _tokens(batch=8):
    return data.make_synthetic_tokens(batch, MODEL.max_seq_len + 1,
                                      MODEL.vocab_size, seed=3)


@pytest.mark.parametrize("pipe,micro", [(2, 0), (4, 0), (2, 4), (4, 8)])
def test_pp_loss_matches_dense(pipe, micro):
    toks = _tokens()
    cfg = _cfg(data=-1, pipe=pipe)
    mesh = build_mesh(cfg.parallel)
    params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params
    pp_loss = make_pp_loss_fn(MODEL, mesh, n_microbatches=micro,
                              dtype=jnp.float32)
    got = jax.jit(pp_loss)(params, toks)

    from tpudist.models import transformer as T
    want = T.loss_fn(params, toks, MODEL, dtype=jnp.float32)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_pp_train_step_matches_dense_trajectory():
    toks = _tokens()
    losses = {}
    for name, par in [("dense", dict(data=-1)),
                      ("pp", dict(data=2, pipe=4))]:
        cfg = _cfg(**par)
        mesh = build_mesh(cfg.parallel)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        ls = []
        for _ in range(3):
            state, l = step(state, (toks,))
            ls.append(float(l))
        losses[name] = ls
    np.testing.assert_allclose(losses["pp"], losses["dense"], rtol=2e-4)
    assert losses["pp"][-1] < losses["pp"][0]


def test_pp_composes_with_fsdp():
    toks = _tokens()
    cfg = _cfg(data=2, pipe=2, fsdp=2)
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    state, l0 = step(state, (toks,))
    state, l1 = step(state, (toks,))
    assert np.isfinite(float(l0)) and float(l1) < float(l0)

    from tpudist.models import transformer as T
    want = T.loss_fn(
        engine.init_state(jax.random.PRNGKey(0), _cfg(data=-1),
                          build_mesh(ParallelConfig(data=-1))).params,
        toks, MODEL, dtype=jnp.float32)
    np.testing.assert_allclose(float(l0), float(want), rtol=1e-5)


def test_pp_rejects_bad_configs():
    cfg = _cfg(data=-1, pipe=2)
    mesh = build_mesh(cfg.parallel)
    # layers not divisible by stages
    bad_model = dataclasses.replace(MODEL, n_layers=3)
    with pytest.raises(ValueError, match="not divisible"):
        make_pp_loss_fn(bad_model, mesh, dtype=jnp.float32)
    # batch not divisible by microbatches
    loss = make_pp_loss_fn(MODEL, mesh, n_microbatches=3,
                           dtype=jnp.float32)
    params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params
    with pytest.raises(ValueError, match="pp_microbatches"):
        loss(params, _tokens())
    # engine-level guards
    with pytest.raises(ValueError, match="do not compose"):
        engine.make_loss_fn(
            _cfg(data=2, pipe=2, context=2), build_mesh(
                ParallelConfig(data=2, pipe=2, context=2)))
    with pytest.raises(ValueError, match="layered"):
        engine.make_loss_fn(
            dataclasses.replace(cfg, model=ModelConfig(name="mlp")), mesh)


@pytest.mark.parametrize("head", ["chunked", "fused"])
def test_pp_head_strategies_match_dense(head):
    """The hoisted single head call (r4: head once per step, not per
    slot) makes --xent-chunks and --fused-xent compose with PP; both must
    reproduce the dense whole-logits loss."""
    toks = _tokens()
    cfg = _cfg(data=-1, pipe=2)
    mesh = build_mesh(cfg.parallel)
    params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params
    kw = (dict(xent_chunks=4) if head == "chunked"
          else dict(fused_xent=True))
    pp_loss = make_pp_loss_fn(MODEL, mesh, dtype=jnp.float32, **kw)

    from tpudist.models import transformer as T
    want = T.loss_fn(params, toks, MODEL, dtype=jnp.float32)
    np.testing.assert_allclose(float(jax.jit(pp_loss)(params, toks)),
                               float(want), rtol=1e-5)


def test_pp_head_flops_do_not_scale_with_slots():
    """r4 fix evidence: the hoisted head costs M microbatch-head units per
    device regardless of slot count; the old per-slot head cost M+S-1.
    With a head-dominated model (vocab 4096 >> d_ff 32), per-device
    compiled FLOPs at S=4 (11 slots) must therefore stay ~equal to S=2
    (9 slots) — under the per-slot head they were ~(11/9 = 1.22×) higher.
    Slot scans are unrolled so cost_analysis counts every slot."""
    model = dataclasses.replace(MODEL, vocab_size=4096, d_ff=32)
    toks = data.make_synthetic_tokens(8, model.max_seq_len + 1,
                                      model.vocab_size, seed=3)
    fl = {}
    for pipe in (2, 4):
        cfg = dataclasses.replace(_cfg(data=-1, pipe=pipe), model=model)
        mesh = build_mesh(cfg.parallel)
        params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params
        pp_loss = make_pp_loss_fn(model, mesh, n_microbatches=8,
                                  dtype=jnp.float32, unroll_slots=True)
        cost = jax.jit(pp_loss).lower(params, toks).compile()
        fl[pipe] = cost.cost_analysis().get("flops")
    if not fl[2] or not fl[4]:
        pytest.skip("backend reports no flops in cost_analysis")
    # S=4 also runs FEWER layer-flops per device (11 slots × 1 layer vs
    # 9 × 2), so with the head M-bound the ratio must sit at ~1; 1.08
    # slack covers bubble-slot elementwise noise
    assert fl[4] < 1.08 * fl[2], (fl[4], fl[2])


def test_pp_bubble_cost_decreases_with_microbatches():
    """The GPipe bubble table (DESIGN.md): per-device slot FLOPs scale as
    (M+S-1)/M — more microbatches amortise the (S-1)-slot fill/drain.
    Measured as compiled per-device FLOPs with the slot scan unrolled, on
    a layer-dominated model (tiny vocab: the head's M-bound cost must not
    mask the slot trend). Also pins the auto default: n_microbatches=0
    resolves to 2S when the batch divides (the M=2S column of this table),
    by asserting its compiled cost equals the explicit M=2S program's."""
    model = dataclasses.replace(MODEL, vocab_size=32, d_ff=256)
    S, batch = 2, 16
    toks = data.make_synthetic_tokens(batch, model.max_seq_len + 1,
                                      model.vocab_size, seed=3)
    cfg = dataclasses.replace(_cfg(batch=batch, data=-1, pipe=S),
                              model=model)
    mesh = build_mesh(cfg.parallel)
    params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params

    def flops(micro):
        pp_loss = make_pp_loss_fn(model, mesh, n_microbatches=micro,
                                  dtype=jnp.float32, unroll_slots=True)
        cost = jax.jit(pp_loss).lower(params, toks).compile()
        return cost.cost_analysis().get("flops")

    fl = {m: flops(m) for m in (S, 2 * S, 4 * S, 0)}
    if not all(fl.values()):
        pytest.skip("backend reports no flops in cost_analysis")
    # strict decrease S -> 2S -> 4S: bubble 33% -> 20% -> 11% of slots
    assert fl[S] > fl[2 * S] > fl[4 * S], fl
    # the slot-FLOP model: cost ratio between M=S and M=2S programs is
    # bounded by their slot ratios (the head contributes equally to both)
    assert fl[S] / fl[2 * S] < (2 * S - 1) / S + 0.05, fl
    # auto default == explicit 2S
    assert fl[0] == fl[2 * S], fl


def test_pp_gqa_matches_dense():
    """Pipeline parallelism over a grouped-query model (4 q heads, 2 kv):
    stage-sharded GQA layers must reproduce the dense loss exactly."""
    gqa = dataclasses.replace(MODEL, n_heads=4, n_kv_heads=2)
    toks = _tokens()
    cfg = dataclasses.replace(_cfg(data=-1, pipe=2), model=gqa)
    mesh = build_mesh(cfg.parallel)
    params = engine.init_state(jax.random.PRNGKey(0), cfg, mesh).params
    pp_loss = make_pp_loss_fn(gqa, mesh, dtype=jnp.float32)
    from tpudist.models import transformer as T
    want = T.loss_fn(params, toks, gqa, dtype=jnp.float32)
    np.testing.assert_allclose(float(jax.jit(pp_loss)(params, toks)),
                               float(want), rtol=1e-5)
