"""Cross-slice plane: hierarchical DP reduce ladder, slice-level MPMD
pipeline, and program-derived DCN byte accounting.

``--cross-slice hierarchical`` is a PERF knob with a correctness
contract: bitwise-identical loss to the flat schedule on the same mesh
(both lower the slice-structured association — parallel.overlap's
module docstring), pinned here the way test_overlap pinned
barrier/bucket parity. The WIN — DCN bytes per step cut by exactly the
slice size — is asserted from the lowered program's collective rows
(obs.devtime.collective_bytes), never from CPU wall clock (PR 12's
observer-effect lesson).
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from tpudist import config as config_lib
from tpudist import data, engine
from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                            TrainConfig)
from tpudist.obs import devtime as devtime_lib
from tpudist.parallel import build_mesh
from tpudist.parallel import mesh as mesh_lib
from tpudist.parallel import overlap as overlap_lib
from tpudist.parallel import pipeline as pipeline_lib
from tpudist.parallel import sharding as shd
from tpudist.tune import search as tune_search
from tpudist.tune.search import Candidate

# every leaf's element count is a multiple of 4, so the hierarchical
# shard tiles evenly (no padding) at slice sizes 1/2/4 and the DCN-byte
# ratio is EXACT — the acceptance relation the program tests pin
MODEL = ModelConfig(name="transformer", vocab_size=64, n_layers=2,
                    d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                    max_seq_len=16)
PP_MODEL = dataclasses.replace(MODEL, n_layers=8)


def _cfg(batch=8, model=MODEL, **kw):
    par = kw.pop("par", {})
    dcfg = kw.pop("data", DataConfig(n_samples=batch))
    return TrainConfig(batch_size=batch, lr=1e-2, seed=0,
                       dtype="float32", data=dcfg, model=model,
                       parallel=ParallelConfig(**par), **kw)


def _tokens(batch=8, model=MODEL, seed=3):
    return data.make_synthetic_tokens(batch, model.max_seq_len + 1,
                                      model.vocab_size, seed=seed)


def _dp_mesh(n=4):
    return build_mesh(ParallelConfig(data=-1), devices=jax.devices()[:n])


def _losses(cfg, mesh, steps=3):
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = _tokens()
    out = []
    for _ in range(steps):
        state, loss = step(state, (toks,))
        out.append(float(loss))
    return out


def _lowered_text(cfg, mesh, toks=None):
    from jax.sharding import PartitionSpec as P

    toks = _tokens() if toks is None else toks
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    body, dp, _ = engine._build_step_body(cfg, mesh)
    assert dp

    def jitted(state, batch):
        bspecs = jax.tree.map(lambda x: shd.batch_spec(x.ndim), batch)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), bspecs),
                             out_specs=(P(), P()),
                             check_vma=False)(state, batch)
    staged = shd.put_batch(mesh, (toks,))
    return jax.jit(jitted).lower(state, staged).as_text()


def _op_counts(text):
    return {op: text.count(f'"stablehlo.{op}"')
            for op in ("all_reduce", "reduce_scatter", "all_gather")}


# ------------------------------------------------------ config resolver


class TestCrossSliceResolver:
    def test_default_is_flat(self):
        assert config_lib.resolve_cross_slice(_cfg()) == "flat"

    def test_env_and_flag_precedence(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_CROSS_SLICE", "hierarchical")
        assert config_lib.resolve_cross_slice(_cfg()) == "hierarchical"
        # the explicit flag outranks the env twin
        assert config_lib.resolve_cross_slice(
            _cfg(cross_slice="flat")) == "flat"

    def test_bad_values_raise(self, monkeypatch):
        with pytest.raises(ValueError, match="cross-slice"):
            config_lib.resolve_cross_slice(_cfg(cross_slice="ladder"))
        monkeypatch.setenv("TPUDIST_CROSS_SLICE", "nope")
        with pytest.raises(ValueError, match="cross-slice"):
            config_lib.resolve_cross_slice(_cfg())

    def test_modes_pinned_to_overlap(self):
        # config repeats the literal so it stays importable before jax
        assert (config_lib.CROSS_SLICE_MODES
                == overlap_lib.CROSS_SLICE_MODES)

    def test_cli_flag_parses(self):
        cfg = config_lib.parse_args(
            ["--cross-slice", "hierarchical", "--train-batch-size", "8"])
        assert cfg.cross_slice == "hierarchical"
        assert config_lib.parse_args(
            ["--train-batch-size", "8"]).cross_slice is None


# ------------------------------------------- slice groups + per-hop fabric


class TestSliceGroups:
    def test_mesh_device_slices_scripted(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        assert mesh_lib.mesh_device_slices(_dp_mesh(4)) == [0, 0, 1, 1]
        monkeypatch.delenv("TPUDIST_SLICE_MAP")
        assert mesh_lib.mesh_device_slices(_dp_mesh(4)) == [0, 0, 0, 0]

    def test_data_slice_groups_two_slices(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        sg = mesh_lib.data_slice_groups(_dp_mesh(4))
        assert sg.n_slices == 2 and sg.slice_size == 2
        # in-slice groups are the ICI reduce-scatter/all-gather groups;
        # cross groups hold the j-th member of every slice (one DCN
        # all-reduce per 1/slice_size shard)
        assert sg.in_slice == ((0, 1), (2, 3))
        assert sg.cross_slice == ((0, 2), (1, 3))

    def test_data_slice_groups_four_slices(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "4")
        sg = mesh_lib.data_slice_groups(_dp_mesh(4))
        assert sg.n_slices == 4 and sg.slice_size == 1
        assert sg.in_slice == ((0,), (1,), (2,), (3,))
        assert sg.cross_slice == ((0, 1, 2, 3),)

    def test_none_without_slice_structure(self, monkeypatch):
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        assert mesh_lib.data_slice_groups(_dp_mesh(4)) is None
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,0,0,0,1,1,1,1")
        # a 4-device submesh of the 8-device world sits on ONE slice
        assert mesh_lib.data_slice_groups(_dp_mesh(4)) is None
        # and a data axis of size 1 has no reduce to shard at all
        mesh1 = build_mesh(ParallelConfig(data=-1),
                           devices=jax.devices()[:1])
        assert mesh_lib.data_slice_groups(mesh1) is None

    def test_unequal_slices_raise(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,0,0,1")
        with pytest.raises(ValueError, match="unequal slice sizes"):
            mesh_lib.data_slice_groups(_dp_mesh(4))

    def test_data_position_spanning_slices_raises(self, monkeypatch):
        # data=2 x fsdp=2 over devices 0..3: data position 0 holds
        # devices {0, 1}; a map splitting that pair makes in-slice
        # grouping undefined
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,1,0,1")
        mesh = build_mesh(ParallelConfig(data=2, fsdp=2),
                          devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="spans slices"):
            mesh_lib.data_slice_groups(mesh)


class TestAxisHops:
    def test_per_hop_fabric_two_slices(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = _dp_mesh(4)
        # slices [0,0,1,1]: the interior boundary hop and the ring wrap
        # cross DCN; the two in-slice hops ride ICI
        assert mesh_lib.axis_hops(mesh, "data") == \
            ["ici", "dcn", "ici", "dcn"]
        # axis_fabric collapses the same axis to dcn (any hop crosses)
        assert mesh_lib.axis_fabric(mesh, "data") == "dcn"

    def test_all_ici_without_slices(self, monkeypatch):
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        assert mesh_lib.axis_hops(_dp_mesh(4), "data") == ["ici"] * 4

    def test_every_hop_dcn_at_slice_size_one(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "4")
        assert mesh_lib.axis_hops(_dp_mesh(4), "data") == ["dcn"] * 4


# ------------------------------------------------------- bitwise parity


class TestCrossSliceParity:
    def test_parity_smoke_two_slices(self, monkeypatch):
        # the fast tier-1 pin; the full mode matrix is the slow test
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = _dp_mesh(4)
        flat = _losses(_cfg(cross_slice="flat", par=dict(data=4)),
                       mesh, steps=1)
        hier = _losses(_cfg(cross_slice="hierarchical",
                            par=dict(data=4)), mesh, steps=1)
        assert flat == hier

    @pytest.mark.slow
    def test_hierarchical_bitwise_matches_flat_and_unsliced(
            self, monkeypatch):
        """On a given slice partition, flat and hierarchical (under
        both --grad-overlap modes) land on ONE bitwise-identical loss
        trajectory: both lower the slice-structured association, so the
        knob moves bytes-on-DCN, never math. Against the UNSLICED
        per-leaf pmean baseline the reduction order differs, so that
        comparison is allclose, not bitwise."""
        mesh = _dp_mesh(4)
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        base = _losses(_cfg(par=dict(data=4)), mesh)
        assert base[-1] < base[0]   # it actually trained
        for sm in ("2", "4"):
            monkeypatch.setenv("TPUDIST_SLICE_MAP", sm)
            matrix = {}
            for cross in ("flat", "hierarchical"):
                for ov in ({}, dict(grad_overlap="bucketed",
                                    grad_bucket_mb=0.001)):
                    got = _losses(_cfg(cross_slice=cross,
                                       par=dict(data=4), **ov), mesh)
                    matrix[(cross, bool(ov))] = got
                    np.testing.assert_allclose(got, base, rtol=1e-5)
            assert len({tuple(v) for v in matrix.values()}) == 1, \
                (sm, matrix)

    def test_single_device_hierarchical_is_inert(self, monkeypatch):
        # a laptop dry-run of a pod launch script must not crash
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        mesh = build_mesh(ParallelConfig(data=-1),
                          devices=jax.devices()[:1])
        got = _losses(_cfg(cross_slice="hierarchical",
                           par=dict(data=1)), mesh)
        base = _losses(_cfg(par=dict(data=1)), mesh)
        assert got == base

    def test_non_dp_mesh_rejects_hierarchical(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        cfg = _cfg(cross_slice="hierarchical", par=dict(data=2, fsdp=2))
        mesh = build_mesh(cfg.parallel, devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="pure-DP"):
            engine.make_train_step(cfg, mesh)

    @pytest.mark.slow
    def test_train_cli_parity_and_devtime_bytes(self, tmp_path,
                                                monkeypatch):
        """End to end through the real train entrypoint on the 8-device
        2-slice mesh: bitwise step-loss parity flat vs hierarchical,
        and the kind=devtime record carries the program-derived byte
        fields with the hierarchical DCN volume cut by the slice size
        (the satellite backfill: the flat record has the same schema)."""
        from tpudist import train as train_lib
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        recs = {}
        for mode in ("flat", "hierarchical"):
            cfg = _cfg(batch=8, epochs=1, log_every=2, profile_window=2,
                       cross_slice=mode,
                       save_dir=str(tmp_path / mode),
                       data=DataConfig(n_samples=32))
            train_lib.run(cfg)
            recs[mode] = [json.loads(l) for l in
                          open(tmp_path / mode / "metrics.jsonl")]
        loss = {m: [r["loss"] for r in rs if r["kind"] == "step"]
                for m, rs in recs.items()}
        assert loss["flat"] and loss["flat"] == loss["hierarchical"]
        dev = {m: [r for r in rs if r["kind"] == "devtime"][0]
               for m, rs in recs.items()}
        for m, d in dev.items():
            assert d["fabric"] == "dcn", (m, d)
            assert d["dcn_bytes_total"] > 0, (m, d)
            assert d["collectives"], (m, d)
        # gradient DCN bytes (rows above the 4-byte loss all-reduce)
        # shrink by EXACTLY the slice size (8 devices / 2 slices = 4)
        def grad_dcn(d):
            return sum(r["dcn_bytes"] for r in d["collectives"]
                       if r["bytes"] > 64)
        assert grad_dcn(dev["flat"]) == 4 * grad_dcn(dev["hierarchical"])


# --------------------------------------------------- program structure


class TestHierarchicalProgram:
    def test_three_phase_ladder_off_mode(self, monkeypatch):
        """--grad-overlap off, 2 slices: ONE ladder for the whole grad
        vector — reduce-scatter (in-slice) → all-reduce (cross-slice,
        plus the loss mean's) → all-gather (in-slice). Flat mode keeps
        two all-reduce phases and no scatter/gather at all."""
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = _dp_mesh(4)
        hier = _op_counts(_lowered_text(
            _cfg(cross_slice="hierarchical", par=dict(data=4)), mesh))
        assert hier == {"all_reduce": 2, "reduce_scatter": 1,
                        "all_gather": 1}
        flat = _op_counts(_lowered_text(
            _cfg(cross_slice="flat", par=dict(data=4)), mesh))
        assert flat == {"all_reduce": 3, "reduce_scatter": 0,
                        "all_gather": 0}

    def test_per_bucket_ladders_compose_with_chain(self, monkeypatch):
        """--grad-overlap bucketed: every bucket lowers to its OWN
        three-phase ladder, chained behind backward the same way the
        single-slice bucket chain pins (one optimization_barrier link
        per bucket boundary)."""
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = _dp_mesh(4)
        cfg = _cfg(cross_slice="hierarchical", grad_overlap="bucketed",
                   grad_bucket_mb=0.03, par=dict(data=4))
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        n_b = overlap_lib.plan_buckets(
            state.params, int(0.03 * 2**20)).n_buckets
        assert n_b > 1   # the bound actually splits this model
        text = _lowered_text(cfg, mesh)
        got = _op_counts(text)
        assert got == {"all_reduce": n_b + 1,   # cross phases + loss
                       "reduce_scatter": n_b, "all_gather": n_b}
        assert text.count("optimization_barrier") == n_b - 1

    def test_ladder_fabrics_and_exact_byte_ratio(self, monkeypatch):
        """The acceptance relation, from program facts: RS/AG rows ride
        ICI, the cross-slice all-reduce rides DCN, and hierarchical DCN
        bytes are EXACTLY flat/slice_size (grad rows; the tiny loss
        all-reduce rides both programs unchanged)."""
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        mesh = _dp_mesh(4)
        slices = mesh_lib.mesh_device_slices(mesh)
        coll = {}
        for cross in ("flat", "hierarchical"):
            text = _lowered_text(_cfg(cross_slice=cross,
                                      par=dict(data=4)), mesh)
            coll[cross] = devtime_lib.collective_bytes(text, slices)
        hier_rows = coll["hierarchical"]["ops"]
        for r in hier_rows:
            if r["op"] in ("reduce_scatter", "all_gather"):
                assert r["fabric"] == "ici" and r["dcn_bytes"] == 0, r
        assert any(r["op"] == "all_reduce" and r["fabric"] == "dcn"
                   for r in hier_rows)

        def grad_dcn(c):
            return sum(r["dcn_bytes"] for r in c["ops"]
                       if r["bytes"] > 64)
        assert grad_dcn(coll["flat"]) == 2 * grad_dcn(
            coll["hierarchical"])
        assert (coll["hierarchical"]["dcn_bytes_total"]
                < coll["flat"]["dcn_bytes_total"])

    def test_single_slice_downgrades_to_flat_program(self, monkeypatch,
                                                     capsys):
        """No slice structure: hierarchical lowers the IDENTICAL
        program flat does (the original per-leaf pmean — no dead
        scatter/gather phases) and says so on stdout."""
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        mesh = _dp_mesh(4)
        hier = _lowered_text(_cfg(cross_slice="hierarchical",
                                  par=dict(data=4)), mesh)
        assert "tpudist: --cross-slice hierarchical downgraded" in \
            capsys.readouterr().out
        flat = _lowered_text(_cfg(cross_slice="flat",
                                  par=dict(data=4)), mesh)
        assert hier == flat
        assert _op_counts(hier)["reduce_scatter"] == 0


# ------------------------------------- collective byte parser (jax-free)


class TestCollectiveBytesParser:
    def test_region_op_with_cross_slice_groups(self):
        text = """\
  %0 = "stablehlo.all_reduce"(%arg0) <{replica_groups = dense<[[0, 2], [1, 3]]> : tensor<2x2xi64>, use_global_device_ids}> ({
  ^bb0(%a: tensor<f32>, %b: tensor<f32>):
    %s = stablehlo.add %a, %b : tensor<f32>
    stablehlo.return %s : tensor<f32>
  }) : (tensor<22xf32>) -> tensor<22xf32>
"""
        out = devtime_lib.collective_bytes(text, [0, 0, 1, 1])
        (row,) = out["ops"]
        assert row["op"] == "all_reduce" and row["dtype"] == "f32"
        assert row["bytes"] == 88 and row["fabric"] == "dcn"
        # every member of both slice-spanning groups pays its payload
        assert row["dcn_bytes"] == 88 * 4
        assert out["dcn_bytes_total"] == 352
        assert out["ici_bytes_total"] == 0

    def test_in_slice_groups_are_ici(self):
        text = """\
  %0 = "stablehlo.reduce_scatter"(%arg0) <{replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>, scatter_dimension = 0 : i64, use_global_device_ids}> ({
  ^bb0(%a: tensor<f32>, %b: tensor<f32>):
    %s = stablehlo.add %a, %b : tensor<f32>
    stablehlo.return %s : tensor<f32>
  }) : (tensor<8xf32>) -> tensor<4xf32>
"""
        out = devtime_lib.collective_bytes(text, [0, 0, 1, 1])
        (row,) = out["ops"]
        # payload is the larger side — the full vector the scatter eats
        assert row["bytes"] == 32 and row["fabric"] == "ici"
        assert out["dcn_bytes_total"] == 0
        assert out["ici_bytes_total"] == 32

    def test_permute_prices_crossing_pairs_only(self):
        text = ('  %1 = "stablehlo.collective_permute"(%arg0) '
                '<{source_target_pairs = dense<[[0, 1], [1, 2], [2, 3], '
                '[3, 0]]> : tensor<4x2xi64>}> : '
                '(tensor<10xf32>) -> tensor<10xf32>\n')
        out = devtime_lib.collective_bytes(text, [0, 0, 1, 1])
        (row,) = out["ops"]
        # the 1->2 boundary hop and the 3->0 wrap cross slices: 2 of 4
        # edges -> "mixed", and only those two pay DCN
        assert row["fabric"] == "mixed"
        assert row["dcn_bytes"] == 40 * 2
        # single-slice table: the same ring is pure ICI
        assert devtime_lib.collective_bytes(
            text, [0, 0, 0, 0])["ops"][0]["fabric"] == "ici"

    def test_splat_dense_and_aggregation(self):
        line = ('  %2 = "stablehlo.all_gather"(%a) <{all_gather_dim = 0 '
                ': i64, replica_groups = dense<0> : tensor<1x1xi64>, '
                'use_global_device_ids}> : '
                '(tensor<4xf32>) -> tensor<4xf32>\n')
        out = devtime_lib.collective_bytes(line * 3, [0, 0])
        (row,) = out["ops"]
        assert row["count"] == 3 and row["fabric"] == "ici"
        assert out["n_collectives"] == 3
        assert out["ici_bytes_total"] == 48

    def test_non_collective_text_is_empty(self):
        out = devtime_lib.collective_bytes(
            "%0 = stablehlo.add %a, %b : tensor<4xf32>\n", [0, 0])
        assert out["ops"] == [] and out["n_collectives"] == 0


# ------------------------------------------- report + live consumers


class TestByteTelemetryConsumers:
    REC = {"kind": "devtime", "exposed_comm_frac": 0.01,
           "fabric": "dcn", "compute_s": 1.0, "comm_s": 0.5,
           "exposed_comm_s": 0.01, "window_s": 1.0, "devices": 1,
           "per_device": [{"device": "TFRT_CPU_0", "compute_s": 1.0,
                           "comm_s": 0.5, "exposed_comm_s": 0.01,
                           "window_s": 1.0, "idle_frac": 0.1}],
           "dcn_bytes_total": 11296,
           "ici_bytes_total": 33888,
           "collectives": [{"op": "all_reduce", "dtype": "f32",
                            "bytes": 11296, "count": 1, "fabric": "dcn",
                            "dcn_bytes": 11296}]}

    def test_report_section_carries_bytes(self):
        from tpudist.obs import report as report_lib
        sec = report_lib.devtime_section([], [self.REC], None)
        assert sec["dcn_bytes_total"] == 11296
        assert sec["ici_bytes_total"] == 33888
        assert sec["collectives"][0]["op"] == "all_reduce"

    def test_report_markdown_renders_byte_line(self):
        from tpudist.obs import report as report_lib
        rep = report_lib.build_report(
            [{"kind": "step", "step": 1, "loss": 1.0}, self.REC], {})
        md = report_lib.to_markdown(rep)
        assert "collective bytes per step (program-derived)" in md
        assert "11296 B over DCN" in md

    def test_live_gauge_exports_dcn_bytes(self, tmp_path):
        from tpudist.obs import live as live_lib
        agg = live_lib.LiveAggregator(out_dir=str(tmp_path), run_id="r",
                                      start_ticker=False)
        agg.ingest(dict(self.REC, run_id="r", host=0))
        status = agg.snapshot()
        assert status["pod"]["dcn_bytes_total"] == 11296
        prom = live_lib.prometheus_text(status)
        assert "tpudist_dcn_bytes_total 11296" in prom


# ------------------------------------------------------- MPMD stage plan


class TestStageSlicePlan:
    def _pipe_mesh(self, stages):
        return build_mesh(ParallelConfig(data=1, pipe=stages),
                          devices=jax.devices()[:stages])

    def test_single_slice_all_ici(self, monkeypatch):
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        plan = pipeline_lib.stage_slice_plan(self._pipe_mesh(4))
        assert plan.n_stages == 4 and plan.fabric == "ici"
        assert plan.dcn_hops == 0
        assert plan.stage_slices == (0, 0, 0, 0)

    def test_aligned_two_slice_mapping(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        plan = pipeline_lib.stage_slice_plan(self._pipe_mesh(4))
        assert plan.stage_slices == (0, 0, 1, 1)
        # one interior boundary hop + the ring wrap cross DCN; chunk
        # rotation between them rides ICI — the MPMD composition rule
        assert plan.hop_fabrics == ("ici", "dcn", "ici", "dcn")
        assert plan.dcn_hops == 2 and plan.fabric == "mixed"

    def test_non_contiguous_mapping_refused(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,1,0,1")
        with pytest.raises(ValueError, match="not contiguous"):
            pipeline_lib.stage_slice_plan(self._pipe_mesh(4))

    def test_stage_spanning_slices_refused(self, monkeypatch):
        # pipe=2 x data=2 over devices 0..3: pipe position 0 holds
        # devices {0, 1}; splitting that pair while the pipe axis
        # crosses DCN is an invalid MPMD mapping
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,1,1,0")
        mesh = build_mesh(ParallelConfig(data=2, pipe=2),
                          devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="span slices"):
            pipeline_lib.stage_slice_plan(mesh)

    def test_slice_replicated_pipelines_stay_valid(self, monkeypatch):
        # DATA crosses slices, every pipe ring stays inside one slice:
        # the replicated-pipelines layout — no refusal, pure ICI hops
        # (data-major device order: ring 0 = devices {0,1}, ring 1 =
        # {2,3}, so "0,0,1,1" puts each ring on its own slice)
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "0,0,1,1")
        mesh = build_mesh(ParallelConfig(data=2, pipe=2),
                          devices=jax.devices()[:4])
        plan = pipeline_lib.stage_slice_plan(mesh)
        assert plan.fabric == "ici" and plan.stage_slices == (None, None)

    def test_loss_fn_carries_plan_and_parity(self, monkeypatch,
                                             capsys):
        """make_pp_loss_fn attaches the stage plan, logs the DCN hops,
        and the slice map changes LABELS only — the pipeline program
        (and therefore the loss) is bitwise-unchanged."""
        mesh = self._pipe_mesh(2)
        cfg = _cfg(model=PP_MODEL, pp_microbatches=4,
                   par=dict(data=1, pipe=2))

        def one_loss():
            state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
            step = engine.make_train_step(cfg, mesh)
            _, loss = step(state, (_tokens(model=PP_MODEL),))
            return float(loss)

        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        base = one_loss()
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        assert one_loss() == base
        loss_fn = pipeline_lib.make_pp_loss_fn(PP_MODEL, mesh,
                                               n_microbatches=4)
        plan = loss_fn.stage_plan
        assert plan.stage_slices == (0, 1) and plan.dcn_hops == 2
        assert "ring hop(s) cross DCN" in capsys.readouterr().out


# ---------------------------------------------------- tuner coordinates


class TestTunerCrossSlice:
    def test_build_space_gates_cross_axis(self, monkeypatch):
        monkeypatch.delenv("TPUDIST_CROSS_SLICE", raising=False)
        cfg = _cfg()
        # multi-slice DP mesh: both modes, led by the resolved mode
        axes = tune_search.build_space(cfg, batch_ways=4,
                                       dp_overlap=True, n_slices=2)
        assert axes["cross_slice"] == ["flat", "hierarchical"]
        lead = tune_search.build_space(
            _cfg(cross_slice="hierarchical"), batch_ways=4,
            dp_overlap=True, n_slices=2)
        assert lead["cross_slice"] == ["hierarchical", "flat"]
        # single slice or non-DP: the coordinate would probe the same
        # program twice — gated off
        assert tune_search.build_space(
            cfg, batch_ways=4, dp_overlap=True,
            n_slices=1)["cross_slice"] == []
        assert tune_search.build_space(
            cfg, batch_ways=4, dp_overlap=False,
            n_slices=2)["cross_slice"] == []

    def test_candidate_applies_cross_slice(self):
        cfg = _cfg()
        assert Candidate(k=4).apply(cfg).cross_slice is None
        assert Candidate(k=4, cross_slice="hierarchical").apply(
            cfg).cross_slice == "hierarchical"

    def test_heuristic_candidate_resolves_cross_slice(self, monkeypatch):
        from tpudist import tune as tune_lib
        monkeypatch.delenv("TPUDIST_CROSS_SLICE", raising=False)
        assert tune_lib._heuristic_candidate(_cfg()).cross_slice == "flat"
        assert tune_lib._heuristic_candidate(
            _cfg(cross_slice="hierarchical")).cross_slice == \
            "hierarchical"

    def test_cache_validates_cross_slice(self):
        from tpudist.tune import cache as cache_mod
        ok = {"k": 8, "grad_accum_steps": 1, "remat": False,
              "staging_budget_mb": None, "grad_bucket_mb": None,
              "pipeline_interleave": 1, "cross_slice": "hierarchical"}
        assert cache_mod._validate_train_tuned(ok)
        assert cache_mod._validate_train_tuned(
            {**ok, "cross_slice": None})
        assert not cache_mod._validate_train_tuned(
            {**ok, "cross_slice": "ladder"})

    def test_fingerprint_covers_cross_slice_and_slices(self,
                                                       monkeypatch):
        from tpudist.tune import cache as cache_mod
        monkeypatch.delenv("TPUDIST_SLICE_MAP", raising=False)
        mesh = _dp_mesh(4)
        fp_flat = cache_mod.fingerprint(_cfg(), mesh)
        fp_hier = cache_mod.fingerprint(
            _cfg(cross_slice="hierarchical"), mesh)
        assert fp_flat != fp_hier
        # the slice partition is part of the tuning situation too: a
        # point tuned on 2 slices must not serve a 4-slice run
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "2")
        fp_2 = cache_mod.fingerprint(_cfg(), mesh)
        monkeypatch.setenv("TPUDIST_SLICE_MAP", "4")
        fp_4 = cache_mod.fingerprint(_cfg(), mesh)
        assert len({fp_flat, fp_2, fp_4}) == 3
