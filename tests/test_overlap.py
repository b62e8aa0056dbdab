"""Overlap plane: bucketed DP grad all-reduce + interleaved pipelining.

The two schedule knobs (``--grad-overlap``, ``--pipeline-interleave``)
are PERF knobs with a correctness contract: bitwise-identical loss to
their baselines (barrier all-reduce, GPipe) on the same mesh — pinned
here the way PR 1/2 pinned superstep parity — plus the scripted 2-slice
DCN labeling, the per-fabric comm grading, and the tuner's new
coordinates.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import config as config_lib
from tpudist import data, engine
from tpudist import rules as rules_lib
from tpudist import verdict as verdict_lib
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig)
from tpudist.parallel import build_mesh
from tpudist.parallel import mesh as mesh_lib
from tpudist.parallel import overlap as overlap_lib
from tpudist.parallel import sharding as shd
from tpudist.parallel.pipeline import make_pp_loss_fn
from tpudist.tune import probe as tune_probe
from tpudist.tune import search as tune_search
from tpudist.tune.search import Candidate

MODEL = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                    d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                    max_seq_len=16)
# pipeline-shaped sibling: 8 layers divide into S*v chunks for
# S in {2, 4}, v in {1, 2}
PP_MODEL = dataclasses.replace(MODEL, n_layers=8)


def _cfg(batch=8, model=MODEL, **kw):
    par = kw.pop("par", {})
    return TrainConfig(batch_size=batch, lr=1e-2, seed=0,
                       dtype="float32", data=DataConfig(n_samples=batch),
                       model=model, parallel=ParallelConfig(**par), **kw)


def _tokens(batch=8, model=MODEL, seed=3):
    return data.make_synthetic_tokens(batch, model.max_seq_len + 1,
                                      model.vocab_size, seed=seed)


def _pipe_mesh(stages):
    """A PURE-pipe mesh over a device subset: this container's jax
    cannot lower collectives under partial-auto shard_map (pipe
    composed with data>1 — see tests/test_pipeline.py's module skip),
    but a mesh where only 'pipe' is real works everywhere."""
    return build_mesh(ParallelConfig(data=1, pipe=stages),
                      devices=jax.devices()[:stages])


# ------------------------------------------------------- bucket planning


class TestBucketPlan:
    def test_reverse_order_and_bound(self):
        tree = [np.zeros((8,), np.float32),   # 32 B, leaf 0
                np.zeros((4,), np.float32),   # 16 B, leaf 1
                np.zeros((2,), np.float32)]   # 8 B,  leaf 2
        plan = overlap_lib.plan_buckets(tree, bucket_bytes=24)
        # reverse flatten order (backward production order), packed
        # under the bound: [2, 1] fits 24 B, leaf 0 spills over
        assert plan.buckets == ((2, 1), (0,))
        assert plan.leaf_bytes == (32, 16, 8)
        assert plan.total_bytes == 56

    def test_oversize_leaf_gets_own_bucket(self):
        tree = [np.zeros((100,), np.float32), np.zeros((1,), np.float32)]
        plan = overlap_lib.plan_buckets(tree, bucket_bytes=64)
        assert plan.buckets == ((1,), (0,))

    def test_nonpositive_bound_is_per_leaf(self):
        tree = [np.zeros((2,), np.float32)] * 3
        plan = overlap_lib.plan_buckets(tree, bucket_bytes=0)
        assert plan.buckets == ((2,), (1,), (0,))

    def test_dict_tree_uses_flatten_order(self):
        tree = {"a": np.zeros((4,), np.float32),
                "z": np.zeros((4,), np.float32)}
        plan = overlap_lib.plan_buckets(tree, bucket_bytes=1)
        # dict flatten order is key-sorted; reverse = z first
        assert plan.buckets == ((1,), (0,))

    def test_leaf_nbytes_from_shape_dtype(self):
        s = jax.ShapeDtypeStruct((3, 5), jnp.bfloat16)
        assert overlap_lib.leaf_nbytes(s) == 30

    def test_defaults_pinned_to_config(self):
        # config repeats the literals so it stays importable before jax
        assert (config_lib.GRAD_OVERLAP_MODES
                == overlap_lib.GRAD_OVERLAP_MODES)
        assert (config_lib.GRAD_BUCKET_MB_DEFAULT
                == overlap_lib.DEFAULT_BUCKET_MB)


# ----------------------------------------------------- config resolvers


class TestResolvers:
    def test_grad_overlap_defaults(self):
        mode, nbytes = config_lib.resolve_grad_overlap(_cfg())
        assert mode == "off"
        assert nbytes == int(config_lib.GRAD_BUCKET_MB_DEFAULT * 2**20)

    def test_grad_overlap_env_and_flag_precedence(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_GRAD_OVERLAP", "bucketed")
        monkeypatch.setenv("TPUDIST_GRAD_BUCKET_MB", "2")
        assert config_lib.resolve_grad_overlap(_cfg()) == (
            "bucketed", 2 * 2**20)
        # explicit flags outrank env
        cfg = _cfg(grad_overlap="off", grad_bucket_mb=1.0)
        assert config_lib.resolve_grad_overlap(cfg) == ("off", 2**20)

    def test_grad_overlap_bad_values_raise(self):
        with pytest.raises(ValueError, match="grad-overlap"):
            config_lib.resolve_grad_overlap(_cfg(grad_overlap="maybe"))
        with pytest.raises(ValueError, match="grad-bucket-mb"):
            config_lib.resolve_grad_overlap(
                _cfg(grad_overlap="bucketed", grad_bucket_mb=-1.0))

    def test_pipeline_interleave_resolution(self, monkeypatch):
        assert config_lib.resolve_pipeline_interleave(_cfg()) == 1
        monkeypatch.setenv("TPUDIST_PIPELINE_INTERLEAVE", "2")
        assert config_lib.resolve_pipeline_interleave(_cfg()) == 2
        assert config_lib.resolve_pipeline_interleave(
            _cfg(pipeline_interleave=4)) == 4
        with pytest.raises(ValueError, match="pipeline-interleave"):
            config_lib.resolve_pipeline_interleave(
                _cfg(pipeline_interleave=-1))

    def test_cli_flags_parse(self):
        cfg = config_lib.parse_args(
            ["--grad-overlap", "bucketed", "--grad-bucket-mb", "2",
             "--pipeline-interleave", "2"])
        assert cfg.grad_overlap == "bucketed"
        assert cfg.grad_bucket_mb == 2.0
        assert cfg.pipeline_interleave == 2


# ------------------------------------------------- scripted slice layout


class TestSliceMap:
    def test_resolve_slice_map_int_form(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        assert mesh_lib.resolve_slice_map(4) == [0, 0, 1, 1]

    def test_resolve_slice_map_list_form(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,1,0,1")
        assert mesh_lib.resolve_slice_map(4) == [0, 1, 0, 1]

    def test_resolve_slice_map_errors(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "3")
        with pytest.raises(ValueError, match="divisible"):
            mesh_lib.resolve_slice_map(4)
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,1")
        with pytest.raises(ValueError, match="entries"):
            mesh_lib.resolve_slice_map(4)
        monkeypatch.delenv("TPUDIST_SLICE_MAP")
        assert mesh_lib.resolve_slice_map(4) is None

    def test_axis_fabric_scripted_two_slices(self, monkeypatch):
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        assert mesh_lib.axis_fabric(mesh, "data") == "ici"
        assert mesh_lib.data_fabric(mesh) == "ici"
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        # the 4-way data axis now spans devices of both scripted slices
        assert mesh_lib.axis_fabric(mesh, "data") == "dcn"
        assert mesh_lib.data_fabric(mesh) == "dcn"
        assert mesh_lib.mesh_fabrics(mesh) == {"data": "dcn"}

    def test_axis_within_one_slice_stays_ici(self, monkeypatch):
        # data=2 over devices {0,1} = scripted slice 0 only
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,0,1,1,0,0,1,1")
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:2])
        assert mesh_lib.axis_fabric(mesh, "data") == "ici"

    def test_bench_sweep_alias_delegates(self, monkeypatch):
        from tpudist.bench import sweep as sweep_mod
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        assert sweep_mod.axis_fabric(mesh, "data") == "dcn"

    def test_data_fabric_singleton_axis_is_ici(self):
        mesh = _pipe_mesh(2)
        assert mesh_lib.data_fabric(mesh) == "ici"


# --------------------------------------------- DP bucketed reduce parity


class TestGradOverlapParity:
    def _losses(self, cfg, mesh, steps=3):
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        toks = _tokens()
        out = []
        for _ in range(steps):
            state, loss = step(state, (toks,))
            out.append(float(loss))
        return out

    def test_bucketed_bitwise_matches_barrier_4dev(self):
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        base = self._losses(_cfg(grad_overlap="off", par=dict(data=4)),
                            mesh)
        for mb in (0.001, 100.0):   # many tiny buckets / one big bucket
            got = self._losses(
                _cfg(grad_overlap="bucketed", grad_bucket_mb=mb,
                     par=dict(data=4)), mesh)
            assert got == base, (mb, got, base)
        assert base[-1] < base[0]   # it actually trained

    def test_bucketed_matches_single_device(self):
        mesh4 = build_mesh(ParallelConfig(data=-1),
                           devices=jax.devices()[:4])
        mesh1 = build_mesh(ParallelConfig(data=-1),
                           devices=jax.devices()[:1])
        l4 = self._losses(_cfg(grad_overlap="bucketed",
                               grad_bucket_mb=0.01, par=dict(data=4)),
                          mesh4)
        l1 = self._losses(_cfg(par=dict(data=1)), mesh1)
        np.testing.assert_allclose(l4, l1, rtol=2e-3, atol=2e-4)

    def test_single_device_bucketed_is_inert(self):
        # a laptop dry-run of a pod launch script must not crash: no
        # data axis, nothing to overlap, same program as off
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:1])
        got = self._losses(_cfg(grad_overlap="bucketed",
                                par=dict(data=1)), mesh)
        base = self._losses(_cfg(par=dict(data=1)), mesh)
        assert got == base

    def test_non_dp_mesh_rejects_bucketed(self):
        cfg = _cfg(grad_overlap="bucketed", par=dict(data=2, fsdp=2))
        mesh = build_mesh(cfg.parallel, devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="pure-DP"):
            engine.make_train_step(cfg, mesh)

    def test_pure_dp_predicate(self):
        assert shd.pure_dp(build_mesh(ParallelConfig(data=-1),
                                      devices=jax.devices()[:4]))
        assert not shd.pure_dp(build_mesh(
            ParallelConfig(data=2, fsdp=2), devices=jax.devices()[:4]))
        assert not shd.pure_dp(build_mesh(
            ParallelConfig(data=-1), devices=jax.devices()[:1]))


class TestProgramStructure:
    def _lowered_text(self, cfg, mesh, toks):
        from jax.sharding import PartitionSpec as P

        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        body, dp, _ = engine._build_step_body(cfg, mesh)
        assert dp

        def jitted(state, batch):
            bspecs = jax.tree.map(lambda x: shd.batch_spec(x.ndim),
                                  batch)
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), bspecs),
                                 out_specs=(P(), P()),
                                 check_vma=False)(state, batch)
        staged = shd.put_batch(mesh, (toks,))
        return jax.jit(jitted).lower(state, staged).as_text()

    def test_bucketed_emits_barrier_chain_off_emits_one(self):
        """The deterministic schedule pin (what a CPU wall-clock
        cannot adjudicate): the lowered program must carry the
        structure the modes promise — ``off`` barriers EVERY grad leaf
        once (no reduce can issue before the whole backward), while
        ``bucketed`` threads one barrier per chain link between bucket
        reduces, which is exactly what stops the collective combiner
        from re-fusing them into the trailing all-reduce."""
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        toks = _tokens()
        n_leaves = len(jax.tree.leaves(engine.init_state(
            jax.random.PRNGKey(0), _cfg(par=dict(data=4)),
            mesh).params))
        off = self._lowered_text(_cfg(grad_overlap="off",
                                      par=dict(data=4)), mesh, toks)
        assert off.count("optimization_barrier") == 1
        # tiny bucket bound -> one bucket per leaf -> n-1 chain links
        buck = self._lowered_text(
            _cfg(grad_overlap="bucketed", grad_bucket_mb=1e-6,
                 par=dict(data=4)), mesh, toks)
        assert buck.count("optimization_barrier") == n_leaves - 1
        # one big bucket has no chain links at all (nothing to order)
        one = self._lowered_text(
            _cfg(grad_overlap="bucketed", grad_bucket_mb=1e4,
                 par=dict(data=4)), mesh, toks)
        assert one.count("optimization_barrier") == 0
        # the reduces themselves are unchanged in count (per-leaf +
        # the loss mean) — only their schedule constraints moved
        assert off.count("all_reduce") == buck.count("all_reduce")


class TestSuperstepComposition:
    def test_one_compile_both_knobs_bitwise_vs_per_step(self):
        """k-step superstep with --grad-overlap bucketed AND
        --pipeline-interleave set (inert at pipe=1) compiles ONCE —
        padded tail included — and reproduces per-step dispatch
        bitwise, exactly PR 1/2's contract for the baseline program."""
        cfg = _cfg(batch=16, grad_overlap="bucketed", grad_bucket_mb=0.01,
                   pipeline_interleave=2, par=dict(data=4))
        cfg = dataclasses.replace(
            cfg, data=DataConfig(n_samples=16 * 6))
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        n_steps, k = 6, 4     # 6 steps over k=4: one full + padded tail
        toks = data.make_synthetic_tokens(n_steps * 16,
                                          MODEL.max_seq_len + 1,
                                          MODEL.vocab_size, 0)
        batches = (toks.reshape(n_steps, 16, -1),)

        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        per_losses = []
        for i in range(n_steps):
            state, loss = step(state,
                               jax.tree.map(lambda a: a[i], batches))
            per_losses.append(float(loss))

        sstate = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        superstep = engine.make_superstep(cfg, mesh, k)
        total = jnp.zeros((), jnp.float32)
        got = []
        staged = shd.put_epoch(mesh, jax.tree.map(
            lambda a: np.concatenate(
                [a, np.zeros((2 * k - n_steps,) + a.shape[1:],
                             a.dtype)]), batches))
        for j in range(2):
            slab = jax.tree.map(lambda a: a[j * k:(j + 1) * k], staged)
            lo, hi = 0, min(n_steps - j * k, k)
            sstate, total, losses = superstep(sstate, total, slab, lo, hi)
            got.extend(float(x) for x in np.asarray(losses)[:hi])
        assert got == per_losses
        assert len(superstep.traces) == 1
        np.testing.assert_allclose(float(total), sum(per_losses),
                                   rtol=1e-6)


# ------------------------------------------------- interleaved pipeline


class TestInterleavedPipeline:
    @pytest.mark.parametrize("stages,v,micro", [(2, 2, 0), (4, 2, 8),
                                                (2, 4, 4)])
    def test_loss_matches_dense(self, stages, v, micro):
        toks = _tokens(model=PP_MODEL)
        mesh = _pipe_mesh(stages)
        cfg = _cfg(model=PP_MODEL, par=dict(data=1, pipe=stages))
        params = engine.init_state(jax.random.PRNGKey(0), cfg,
                                   mesh).params
        pp = make_pp_loss_fn(PP_MODEL, mesh, n_microbatches=micro,
                             dtype=jnp.float32, interleave=v)
        got = float(jax.jit(pp)(params, toks))
        from tpudist.models import transformer as T
        want = float(T.loss_fn(params, toks, PP_MODEL,
                               dtype=jnp.float32))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_interleaved_bitwise_matches_gpipe(self):
        """The parity oracle: the v=2 schedule computes the exact same
        per-microbatch layer sequence as GPipe, so the loss agrees
        BITWISE (same kernels, same order, different slot timetable)."""
        toks = _tokens(model=PP_MODEL)
        mesh = _pipe_mesh(4)
        cfg = _cfg(model=PP_MODEL, par=dict(data=1, pipe=4))
        params = engine.init_state(jax.random.PRNGKey(0), cfg,
                                   mesh).params
        losses = {}
        for v in (1, 2):
            pp = make_pp_loss_fn(PP_MODEL, mesh, n_microbatches=8,
                                 dtype=jnp.float32, interleave=v)
            losses[v] = float(jax.jit(pp)(params, toks))
        assert losses[1] == losses[2], losses

    def test_train_trajectory_matches_gpipe(self):
        toks = _tokens(model=PP_MODEL)
        mesh = _pipe_mesh(4)
        traj = {}
        for v in (1, 2):
            cfg = _cfg(model=PP_MODEL, pipeline_interleave=v,
                       pp_microbatches=8, par=dict(data=1, pipe=4))
            state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
            step = engine.make_train_step(cfg, mesh)
            ls = []
            for _ in range(3):
                state, l = step(state, (toks,))
                ls.append(float(l))
            traj[v] = ls
        np.testing.assert_allclose(traj[2], traj[1], rtol=1e-6)
        assert traj[2][-1] < traj[2][0]

    def test_rejects_bad_configs(self):
        mesh = _pipe_mesh(2)
        with pytest.raises(ValueError, match="interleave"):
            make_pp_loss_fn(PP_MODEL, mesh, dtype=jnp.float32,
                            interleave=0)
        # 8 layers over pipe=2 * v=8 = 16 chunks: does not divide
        with pytest.raises(ValueError, match="not divisible"):
            make_pp_loss_fn(PP_MODEL, mesh, dtype=jnp.float32,
                            interleave=8)
        # microbatches must group by S when interleaving
        loss = make_pp_loss_fn(PP_MODEL, mesh, n_microbatches=3,
                               dtype=jnp.float32, interleave=2)
        params = engine.init_state(
            jax.random.PRNGKey(0),
            _cfg(batch=9, model=PP_MODEL, par=dict(data=1, pipe=2)),
            mesh).params
        with pytest.raises(ValueError, match="groups of pipe"):
            loss(params, _tokens(batch=9, model=PP_MODEL))

    def test_interleave_cuts_per_device_slot_flops(self):
        """The bubble model: per-device slot FLOPs scale as
        (v·M+S−1)/(v·M) — at S=2, M=4 the v=2 schedule runs 9 chunk
        slots of L/4 layers vs GPipe's 5 slots of L/2, a 10% layer-FLOP
        cut. Measured as compiled FLOPs with the slot scan unrolled on
        a layer-dominated model (tiny vocab — the head contributes
        equally to both programs)."""
        model = dataclasses.replace(PP_MODEL, vocab_size=32, d_ff=256)
        S, M, batch = 2, 4, 8
        mesh = _pipe_mesh(S)
        cfg = dataclasses.replace(
            _cfg(batch=batch, par=dict(data=1, pipe=S)), model=model)
        params = engine.init_state(jax.random.PRNGKey(0), cfg,
                                   mesh).params
        toks = _tokens(batch=batch, model=model)
        fl = {}
        for v in (1, 2):
            pp = make_pp_loss_fn(model, mesh, n_microbatches=M,
                                 dtype=jnp.float32, interleave=v,
                                 unroll_slots=True)
            cost = jax.jit(pp).lower(params, toks).compile()
            fl[v] = cost.cost_analysis().get("flops")
        if not fl[1] or not fl[2]:
            pytest.skip("backend reports no flops in cost_analysis")
        assert fl[2] < fl[1], fl
        # slot model bound: ratio >= (vM+S-1)/(v(M+S-1)) minus the
        # equal-head slack
        assert fl[2] / fl[1] < 1.0, fl

    def test_engine_wires_interleave_from_cfg(self):
        """make_loss_fn passes cfg.pipeline_interleave through — a
        non-dividing count must surface the pipeline's own error."""
        cfg = _cfg(model=PP_MODEL, pipeline_interleave=8,
                   par=dict(data=1, pipe=2))
        mesh = _pipe_mesh(2)
        with pytest.raises(ValueError, match="not divisible"):
            engine.make_loss_fn(cfg, mesh)


# ------------------------------------------- per-fabric comm grading


class TestFabricGrading:
    def test_comm_status_fabric_thresholds(self):
        mid = (rules_lib.COMM_EXPOSED_MAX
               + rules_lib.COMM_EXPOSED_MAX_DCN) / 2
        from tpudist.obs import devtime as devtime_lib
        assert devtime_lib.comm_status(mid) == verdict_lib.FAIL
        assert devtime_lib.comm_status(mid, fabric="ici") == \
            verdict_lib.FAIL
        assert devtime_lib.comm_status(mid, fabric="dcn") == \
            verdict_lib.SUCCESS
        assert devtime_lib.comm_status(None, fabric="dcn") == \
            verdict_lib.UNGATEABLE
        # the verdict delegator forwards the fabric
        assert verdict_lib.comm_status(mid, fabric="dcn") == \
            verdict_lib.SUCCESS
        # explicit max_frac still wins over the fabric default
        assert devtime_lib.comm_status(mid, 0.9, fabric="ici") == \
            verdict_lib.SUCCESS

    def test_report_devtime_section_grades_by_record_fabric(self):
        from tpudist.obs import report as report_lib
        mid = (rules_lib.COMM_EXPOSED_MAX
               + rules_lib.COMM_EXPOSED_MAX_DCN) / 2
        rec = {"kind": "devtime", "exposed_comm_frac": mid,
               "fabric": "dcn", "compute_s": 1.0, "comm_s": 0.5,
               "exposed_comm_s": mid, "window_s": 1.0, "devices": 1,
               "per_device": []}
        sec = report_lib.devtime_section([], [rec], None)
        assert sec["comm_status"] == verdict_lib.SUCCESS
        assert sec["fabric"] == "dcn"
        sec_ici = report_lib.devtime_section(
            [], [{**rec, "fabric": "ici"}], None)
        assert sec_ici["comm_status"] == verdict_lib.FAIL


# ---------------------------------------------------- tuner coordinates


class TestTunerCoordinates:
    def test_build_space_gates_bucket_axis(self):
        cfg = _cfg(grad_overlap="bucketed", grad_bucket_mb=2.0)
        axes = tune_search.build_space(cfg, batch_ways=4, dp_overlap=True)
        assert axes["grad_bucket_mb"][0] == 2.0
        assert set(tune_search.GRAD_BUCKET_LADDER_MB) <= set(
            axes["grad_bucket_mb"]) | {2.0}
        # off, or a non-DP mesh, owns no bucket axis
        assert tune_search.build_space(
            _cfg(), batch_ways=4, dp_overlap=True)["grad_bucket_mb"] == []
        assert tune_search.build_space(
            cfg, batch_ways=4, dp_overlap=False)["grad_bucket_mb"] == []

    def test_build_space_gates_interleave_axis(self):
        cfg = _cfg(model=PP_MODEL, par=dict(data=1, pipe=2))
        axes = tune_search.build_space(cfg, batch_ways=1, pipe_stages=2)
        # 8 layers / 2 stages: v in {1, 2, 4} divide
        assert axes["pipeline_interleave"] == [1, 2, 4]
        # a non-S-divisible explicit microbatch count disables the axis
        cfg_m = dataclasses.replace(cfg, pp_microbatches=3)
        assert tune_search.build_space(
            cfg_m, batch_ways=1, pipe_stages=2)["pipeline_interleave"] \
            == []
        # no pipe axis, no interleave axis
        assert tune_search.build_space(
            cfg, batch_ways=1)["pipeline_interleave"] == []

    def test_candidate_apply_and_key(self):
        cfg = _cfg(grad_overlap="bucketed")
        a = Candidate(k=4, grad_bucket_mb=1.0)
        b = Candidate(k=4, grad_bucket_mb=16.0)
        assert a.apply(cfg).grad_bucket_mb == 1.0
        assert a.apply(cfg).pipeline_interleave == 0  # untouched
        c = Candidate(k=4, pipeline_interleave=2)
        assert c.apply(cfg).pipeline_interleave == 2
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        plan = data.plan_epoch(
            (_tokens(batch=32),), batch_size=8, seed=0, epoch=0)
        ka = tune_probe.candidate_key(cfg, mesh, a, plan, 4)
        kb = tune_probe.candidate_key(cfg, mesh, b, plan, 4)
        assert ka != kb   # different bucket sizes are different programs

    def test_heuristic_candidate_carries_overlap_knobs(self):
        from tpudist import tune as tune_lib
        cand = tune_lib._heuristic_candidate(
            _cfg(grad_overlap="bucketed", grad_bucket_mb=2.0,
                 pipeline_interleave=2))
        assert cand.grad_bucket_mb == 2.0
        assert cand.pipeline_interleave == 2
        cand_off = tune_lib._heuristic_candidate(_cfg())
        assert cand_off.grad_bucket_mb is None
        assert cand_off.pipeline_interleave == 1

    def test_search_commits_fastest_bucket_never_slower(self):
        class _Res:
            feasible = True
            counted = True
            spread = 0.0

            def __init__(self, sps):
                self.steps_per_sec = sps

        sps_by_bucket = {2.0: 10.0, 1.0: 14.0, 4.0: 12.0, 16.0: 9.0}
        start = Candidate(k=8, grad_bucket_mb=2.0)
        out = tune_search.coordinate_search(
            start, {"grad_bucket_mb": [2.0, 1.0, 4.0, 16.0]},
            lambda c: _Res(sps_by_bucket[c.grad_bucket_mb]),
            trial_budget=8)
        assert out.best.grad_bucket_mb == 1.0
        # never-slower guarantee: an all-worse axis keeps the start
        slower = {2.0: 10.0, 1.0: 5.0, 4.0: 6.0, 16.0: 4.0}
        out2 = tune_search.coordinate_search(
            start, {"grad_bucket_mb": [2.0, 1.0, 4.0, 16.0]},
            lambda c: _Res(slower[c.grad_bucket_mb]), trial_budget=8)
        assert out2.best == start

    def test_cache_validates_overlap_knobs(self):
        from tpudist.tune import cache as cache_mod
        ok = {"k": 8, "grad_accum_steps": 1, "remat": False,
              "staging_budget_mb": None, "grad_bucket_mb": 4.0,
              "pipeline_interleave": 2}
        assert cache_mod._validate_train_tuned(ok)
        assert not cache_mod._validate_train_tuned(
            {**ok, "grad_bucket_mb": -1.0})
        assert not cache_mod._validate_train_tuned(
            {**ok, "pipeline_interleave": -2})
        # entries from before the knobs existed still validate (their
        # fingerprints changed anyway — grad_overlap/pp fields)
        old = {k: v for k, v in ok.items()
               if k not in ("grad_bucket_mb", "pipeline_interleave")}
        assert cache_mod._validate_train_tuned(old)

    def test_fingerprint_covers_grad_overlap_mode(self):
        from tpudist.tune import cache as cache_mod
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:4])
        fp_off = cache_mod.fingerprint(_cfg(), mesh)
        fp_on = cache_mod.fingerprint(_cfg(grad_overlap="bucketed"),
                                      mesh)
        assert fp_off != fp_on


# ------------------------------------------------ devtime CPU op threads


def test_devtime_parses_eigen_pool_threads():
    """Newer jaxlib CPU thunk runtimes put HLO op events on the
    tf_XLAEigen compute pool, not tf_XLATfrtCpuClient — both fold into
    the one synthetic host track (all-reduce classified comm)."""
    from tpudist.obs import devtime as devtime_lib
    doc = {"traceEvents": [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "tf_XLAEigen/123"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
         "args": {"name": "tf_XLATfrtCpuClient/9"}},
        {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name",
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 5.0,
         "name": "dot.1"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 5.0, "dur": 5.0,
         "name": "all-reduce.3"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 10.0, "dur": 2.0,
         "name": "fusion.2"},
        {"ph": "X", "pid": 7, "tid": 3, "ts": 0.0, "dur": 99.0,
         "name": "dot.ignored"},
    ]}
    tracks = devtime_lib.device_op_tracks(doc)
    assert list(tracks) == ["host:CPU"]
    names = sorted(op for _, _, op in tracks["host:CPU"])
    assert names == ["all-reduce.3", "dot.1", "fusion.2"]
