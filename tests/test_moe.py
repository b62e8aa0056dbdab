"""MoE model + expert parallelism on the virtual 8-device mesh.

Dense-dispatch routing is pure math (no RNG, no data-dependent shapes), so
expert-parallel execution must agree exactly with single-device execution;
these tests pin that, plus the routing/capacity/aux invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import data, engine
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig)
from tpudist.models import moe
from tpudist.parallel import build_mesh

MODEL = ModelConfig(name="moe", vocab_size=128, n_layers=2, d_model=32,
                    n_heads=2, n_kv_heads=2, d_ff=48, max_seq_len=16,
                    n_experts=4, expert_top_k=2, capacity_factor=2.0)


def _cfg(batch=8, model=MODEL, **par):
    return TrainConfig(batch_size=batch, lr=1e-2, seed=0, dtype="float32",
                       data=DataConfig(n_samples=batch), model=model,
                       parallel=ParallelConfig(**par))


def _tokens(batch=8):
    return data.make_synthetic_tokens(batch, MODEL.max_seq_len + 1,
                                      MODEL.vocab_size, seed=5)


def test_route_keeps_all_pairs_under_ample_capacity():
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(0), (12, 4)), -1)
    disp, comb, assigned = moe._route(probs, k=2, cap=12 * 2)
    assert disp.shape == (12, 4, 24)
    np.testing.assert_allclose(float(disp.sum()), 12 * 2)
    np.testing.assert_allclose(float(assigned.sum()), 12 * 2)
    # combine gates renormalise to 1 per token
    np.testing.assert_allclose(np.asarray(comb.sum(axis=(1, 2))),
                               np.ones(12), rtol=1e-5)


def test_route_drops_overflow_deterministically():
    # all tokens prefer expert 0; capacity 3 keeps the first 3 pairs
    probs = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (8, 1))
    disp, _, assigned = moe._route(probs, k=1, cap=3)
    kept = np.asarray(disp.sum(axis=(1, 2)))
    np.testing.assert_allclose(kept, [1, 1, 1, 0, 0, 0, 0, 0])
    # aux fractions count PRE-drop assignments: the overload stays visible
    np.testing.assert_allclose(np.asarray(assigned), [8, 0, 0, 0])


def test_uniform_router_aux_is_one():
    probs = jnp.full((16, 4), 0.25)
    _, _, assigned = moe._route(probs, k=2, cap=32)
    f_e = assigned / 32
    p_e = probs.mean(axis=0)
    np.testing.assert_allclose(float(4 * jnp.sum(f_e * p_e)), 1.0,
                               rtol=1e-5)


def test_grouped_routing_matches_single_group():
    # t=128 with group 32 vs one group: same FFN output when capacity is
    # ample in both (per-group cap scales down with g)
    cfg_g = dataclasses.replace(MODEL, moe_group_size=32)
    cfg_1 = dataclasses.replace(MODEL, moe_group_size=0)
    assert moe.group_size(cfg_g, 128) == 32
    assert moe.group_size(cfg_1, 128) == 128
    # non-divisor: largest divisor at or below wins (memory stays bounded)
    assert moe.group_size(dataclasses.replace(MODEL, moe_group_size=48),
                          128) == 32
    assert moe.group_size(dataclasses.replace(MODEL, moe_group_size=100),
                          96) == 96
    # near-prime: tiny divisors would degenerate capacity/aux semantics —
    # fall back to one global group instead
    assert moe.group_size(dataclasses.replace(MODEL, moe_group_size=48),
                          127) == 127
    params = moe.init(jax.random.PRNGKey(0), MODEL)
    toks = _tokens()
    l_g = moe.loss_fn(params, toks, cfg_g, dtype=jnp.float32)
    l_1 = moe.loss_fn(params, toks, cfg_1, dtype=jnp.float32)
    # group-local capacity changes which overflow pairs drop, but with
    # cf=2.0 and near-uniform random routing the losses stay close
    np.testing.assert_allclose(float(l_g), float(l_1), rtol=5e-2)


def test_loss_finite_and_trains():
    cfg = _cfg(data=-1)
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = _tokens()
    losses = []
    for _ in range(5):
        state, l = step(state, (toks,))
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_expert_parallel_matches_single_device():
    # all three run the jit+shardings path (global-batch routing); the
    # explicit-DP shard_map path routes per shard and is a semantically
    # different (group-local) MoE — see moe.py docstring
    toks = _tokens()
    got = {}
    for name, par in [("ep1", dict(data=1, fsdp=8)),
                      ("ep2", dict(data=4, expert=2)),
                      ("ep4_fsdp", dict(data=1, fsdp=2, expert=4))]:
        cfg = _cfg(**par)
        mesh = build_mesh(cfg.parallel)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        ls = []
        for _ in range(3):
            state, l = step(state, (toks,))
            ls.append(float(l))
        got[name] = ls
    np.testing.assert_allclose(got["ep2"], got["ep1"], rtol=2e-5)
    np.testing.assert_allclose(got["ep4_fsdp"], got["ep1"], rtol=2e-5)


def test_moe_context_parallel_matches_global():
    """MoE + CP (both impls): with ample capacity no routed pair drops,
    so shard-local routing matches the global-batch jit path exactly."""
    ample = dataclasses.replace(MODEL, capacity_factor=4.0)
    toks = _tokens()
    got = {}
    runs = [("global", dict(data=1, fsdp=8), "ring"),
            ("cp_ring", dict(data=2, fsdp=2, context=2), "ring"),
            ("cp_ulysses", dict(data=2, fsdp=2, context=2), "ulysses")]
    for name, par, cp in runs:
        cfg = dataclasses.replace(_cfg(model=ample, **par), cp_impl=cp)
        mesh = build_mesh(cfg.parallel)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        ls = []
        for _ in range(3):
            state, l = step(state, (toks,))
            ls.append(float(l))
        got[name] = ls
    np.testing.assert_allclose(got["cp_ring"], got["global"], rtol=2e-4)
    np.testing.assert_allclose(got["cp_ulysses"], got["global"],
                               rtol=2e-4)


def test_moe_context_composes_with_expert_axis():
    """The full zoo in one program: dp x expert x context — pinned
    against the same CP layout without expert sharding (identical math;
    the expert axis only changes where the FFN weights live)."""
    ample = dataclasses.replace(MODEL, capacity_factor=4.0)
    toks = _tokens()
    got = {}
    for name, par in [("ep1", dict(data=2, fsdp=2, context=2)),
                      ("ep2", dict(data=2, expert=2, context=2))]:
        cfg = _cfg(model=ample, **par)
        mesh = build_mesh(cfg.parallel)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        ls = []
        for _ in range(3):
            state, l = step(state, (toks,))
            ls.append(float(l))
        got[name] = ls
    np.testing.assert_allclose(got["ep2"], got["ep1"], rtol=2e-5)
    assert got["ep2"][-1] < got["ep2"][0]


def test_moe_pipeline_matches_global():
    """MoE + pipeline: per-microbatch group-local routing; with ample
    capacity the dispatch/xent match the global jit path (the aux term is
    mildly partition-dependent, hence the looser tolerance)."""
    ample = dataclasses.replace(MODEL, capacity_factor=4.0)
    toks = _tokens()
    got = {}
    for name, par in [("global", dict(data=1, fsdp=8)),
                      ("pp", dict(data=2, pipe=2, fsdp=2))]:
        cfg = _cfg(model=ample, **par)
        mesh = build_mesh(cfg.parallel)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        ls = []
        for _ in range(3):
            state, l = step(state, (toks,))
            ls.append(float(l))
        got[name] = ls
    np.testing.assert_allclose(got["pp"], got["global"], rtol=2e-3)
    assert got["pp"][-1] < got["pp"][0]

    # with the aux term off, the comparison is EXACT (same dispatch/xent):
    # pins that bubble-slot garbage never leaks into the objective
    noaux = dataclasses.replace(ample, router_aux_weight=0.0)
    vals = {}
    for name, par in [("global", dict(data=1, fsdp=8)),
                      ("pp", dict(data=2, pipe=2, fsdp=2))]:
        cfg = _cfg(model=noaux, **par)
        mesh = build_mesh(cfg.parallel)
        fresh = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        loss_fn = engine.make_loss_fn(cfg, mesh, constrain_logits=(
            name == "global"))
        vals[name] = float(jax.jit(loss_fn)(fresh.params, (toks,)))
    np.testing.assert_allclose(vals["pp"], vals["global"], rtol=1e-6)


def test_capacity_is_static_and_sane():
    assert moe.capacity(MODEL, 64) == 64  # 64·2·2.0/4
    tight = dataclasses.replace(MODEL, capacity_factor=0.5)
    assert moe.capacity(tight, 64) == 16
    assert moe.capacity(dataclasses.replace(MODEL, n_experts=1000), 4) >= 1


def test_expert_axis_rejected_for_non_moe_models():
    from tpudist.models import transformer  # noqa: F401  (registry warm)
    cfg = _cfg(data=4, expert=2,
               model=dataclasses.replace(MODEL, name="transformer"))
    mesh = build_mesh(cfg.parallel)
    with pytest.raises(ValueError, match="expert"):
        engine.make_loss_fn(cfg, mesh)


def test_moe_gqa_expert_parallel_matches_single_device():
    """MoE with GROUPED-QUERY attention (4 q heads, 2 kv heads) under
    expert parallelism must reproduce the unsharded trajectory — the
    bench matrix carries a moe_gqa row; this pins the composition's
    correctness on the CPU mesh (the chip row only proves it runs
    fast)."""
    gqa = dataclasses.replace(MODEL, n_heads=4, n_kv_heads=2)
    losses = {}
    for name, par in [("ep", dict(data=-1, expert=4)),
                      ("single", dict(data=1))]:
        cfg = _cfg(model=gqa, **par)
        devs = jax.devices()[:8] if name == "ep" else jax.devices()[:1]
        mesh = build_mesh(cfg.parallel, devices=devs)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        toks = _tokens()
        traj = []
        for _ in range(3):
            state, l = step(state, (toks,))
            traj.append(float(l))
        losses[name] = traj
    np.testing.assert_allclose(losses["ep"], losses["single"],
                               rtol=2e-4, atol=2e-4)
