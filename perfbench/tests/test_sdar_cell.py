"""The cell ``sdar-serve-longgen-sat`` at rehearsal size on the CPU: the
line it prints in both trace modes, what has to come out as NOT correct
does (the fp8 control, the five planted faults and the two planted orders of
unmasking in the reference's place, a token altered under the timed path), the program itself comes out
correct under limits as tight as the tiny size allows (in float32 it serves
the reference's own first choices in the reference's own order: every gap
is 0), the counts add up, and the manifest keeps the rules of form."""

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

os.environ.setdefault("PERFBENCH_REHEARSAL", "1")

from perfbench import run as run_mod                        # noqa: E402
from perfbench.lib import flops_sdar as flops               # noqa: E402
from perfbench.lib import manifest                          # noqa: E402
from perfbench.lib import reference_sdar as ref             # noqa: E402
from perfbench.lib import serve_sdar_entry as entry         # noqa: E402
from perfbench.readers import paged_attn_roofline, span_ratio  # noqa: E402

MAN = manifest.load()
CELL, CONFIG = "sdar-serve-longgen-sat", "sdar-30b-a3b-l7"
TIGHT = {"logit_gap_mean": 1e-5, "unmask_conf_gap_mean": 1e-9}
VARIANTS = [("fp8", None)] + [(None, f) for f in ref.FAULTS
                              + ref.ORDER_FAULTS]


def ctx_for(fault=None, seed=11):
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0, trace=0)
    ctx = run_mod.Ctx(ns, MAN)
    ctx.fault = fault
    ctx.traffic = dict(ctx.traffic, limits=TIGHT, check_requests=6)
    ctx.arm_compile_count = lambda on: None
    ctx.workdir = tempfile.mkdtemp(prefix="perfbench-test-")
    return ctx


def correct(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())


def full_config():
    with open(os.path.join(manifest.ROOT, manifest.config_entry(
            MAN, CONFIG)["file"])) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served():
    ctx = ctx_for()
    res = entry.window(ctx)
    return ctx, res, entry.score(ctx, res["sample"], VARIANTS)


def test_the_program_is_correct_under_the_tight_limits(served):
    ctx, res, got = served
    assert res["failed"] == 0 and len(got["gaps"]) > 40
    c = entry.compared(ctx, got["gaps"], got["conf_gaps"], res["failed"])
    assert correct(c), c
    assert c["logit_gap_mean"]["value"] == 0.0
    assert c["unmask_conf_gap_mean"]["value"] == 0.0


@pytest.mark.parametrize("name", ["fp8"] + list(ref.FAULTS))
def test_control_and_planted_faults_are_not_correct(served, name):
    ctx, res, got = served
    c = entry.compared(ctx, got[name + "_gaps"], got[name + "_conf_gaps"],
                       res["failed"])
    assert not correct(c), c
    assert c["logit_gap_mean"]["value"] > 100 * TIGHT["logit_gap_mean"]


@pytest.mark.parametrize("name", ref.ORDER_FAULTS)
def test_a_planted_order_of_unmasking_is_not_correct(served, name):
    """Only ``unmask_conf_gap_mean`` can see another order: every token
    served under it is still the reference's first choice."""
    ctx, res, got = served
    c = entry.compared(ctx, got[name + "_gaps"], got[name + "_conf_gaps"],
                       res["failed"])
    assert c["logit_gap_mean"]["value"] == 0.0
    assert not correct(c), c
    assert c["unmask_conf_gap_mean"]["value"] \
        > 1e5 * TIGHT["unmask_conf_gap_mean"]


def test_a_token_altered_under_the_timed_path_is_not_correct():
    ctx = ctx_for(fault="token_altered")
    res = entry.run(ctx)
    assert not correct(res["compared"]), res["compared"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_run_prints_a_valid_line(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "perfbench/run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PERFBENCH_REHEARSAL="1"))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert not manifest.validate_line(
        line, MAN, CELL, trace,
        [n for n in manifest.expected(MAN, CELL, trace)
         if trace and run_mod.metric_spec(n).get("needs_chip")])
    want = {"serve_tokens_per_s", "setup_s"} if not trace else {
        "forwards_per_token.longgen", "serve_mfu.longgen",
        "block_dispatch_ms_p50.longgen", "moe_pairs_per_expert.longgen"}
    assert want <= set(line["metrics"])
    if trace:
        assert line["metrics"]["forwards_per_token.longgen"]["value"] >= 1.25


def test_the_cells_files_state_the_cut_and_the_counts_add_up():
    full = full_config()
    assert flops.n_params(full) == 4_984_176_384
    entry_ = manifest.config_entry(MAN, CONFIG)
    assert full["reduced"] == entry_["reduced"] == ["num_hidden_layers"]
    assert full["published"] == {"num_hidden_layers": 48}
    assert full["source"] == entry_["source"]
    # every number of the catalog's row stands in the file under its key
    for key, want in {"hidden_size": 2048, "num_attention_heads": 32,
                      "num_key_value_heads": 4, "head_dim": 128,
                      "moe_intermediate_size": 768, "num_experts": 128,
                      "num_experts_per_tok": 8, "vocab_size": 151936,
                      "intermediate_size": 6144, "rope_theta": 1000000,
                      "rms_norm_eps": 1e-06}.items():
        assert full[key] == want
    # block-causal keys: position p sees all up to its block's end
    assert flops.keys_seen(0, 7, 4) == 4 * 4 + 4 * 8
    assert flops.keys_seen(5, 5, 4) == 8
    one = flops.forward_flops(full, 1, 6) \
        - 2 * flops.matmul_params_per_token(full)
    assert one == 4 * 32 * 128 * 7 * 8
    # 8 experts a token a layer: 3.4 B weights in products a token
    assert flops.matmul_params_per_token(full) == 7 * (
        18_874_368 + 262_144 + 8 * 4_718_592) + 151_936 * 2048
    # the cache: 14,336 B a token
    assert 7 * 2 * 4 * 128 * 2 == 14_336
    mc = entry.model_config(ctx_for())
    assert (mc.block_length, mc.denoise_steps) == (4, 4)
    assert mc.n_experts == 8 and mc.mask_token_id == 255


def test_the_mix_never_draws_the_mask_token():
    ctx = ctx_for()
    reqs, _ = entry.requests_of(ctx)
    mask = ctx.config["mask_token_id"]
    seen = {int(t) for _, toks, pl, _ in reqs for t in toks[:pl]}
    assert mask not in seen and max(seen) <= ctx.config["vocab_size"] - 1
    full = json.load(open(os.path.join(manifest.BENCH_DIR, "traffic",
                                       "longgen-sat.json")))
    e = full["engine"]
    assert (e["slots"], e["page_tokens"], e["pages"], e["prompt_pad"],
            e["max_seq"]) == (128, 64, 3072, 1024, 3072)
    assert full["rate_rps"] == pytest.approx(1.25 * full["knee_rps"])


@pytest.mark.parametrize("cell,config", [
    ("cmdaplus-serve-docqa-sat", "command-a-plus-l4e16"), (CELL, CONFIG)])
def test_what_a_cell_adds_to_the_manifest_keeps_the_rules_of_form(cell,
                                                                  config):
    # the driver refuses the whole file for one line over 200 characters
    # (PR 27's first hand-in: a `why` of 206)
    cfg = manifest.config_entry(MAN, config)
    row = next(w for w in MAN["workloads"] if w["name"] == cell)
    for line in (cfg["why"], cfg["source"], row["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable()
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(row) == {"name", "config", "traffic", "chips", "why"}
    mine = [m for m in MAN["per_layer"] if m.get("workloads") == [cell]]
    assert mine
    for m in mine:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in manifest.end_to_end(MAN, cell)
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", m["name"] + ".json"))
    assert len(json.dumps(MAN, indent=1)) < 64 * 1024


def test_the_metric_files_scope_words_are_the_programs():
    from tpudist import scopes
    words = {w for s in scopes.MODEL_SCOPES + scopes.BLOCK_SCOPES
             for w in s.split("/")} \
        - {w for s in scopes.SCOPES for w in s.split("/")}
    seen = 0
    for m in MAN["per_layer"]:
        spec = run_mod.metric_spec(m["name"])
        if spec["reader"] == "denoise_scope_time":
            assert set(spec["params"]["words"]) == words
            assert m["workloads"] == [CELL]
            # the pattern names scopes the program can enter
            assert spec["params"]["scope"].startswith("^denoise/")
            seen += 1
    assert seen == 6


# ------------------------------------------------------------- readers


def test_span_ratio_reads_forwards_a_token():
    spans = [{"name": "decode_step", "t0_us": 10.0, "t1_us": 20.0,
              "args": {"forwards": 640, "tokens_emitted": 512}},
             {"name": "decode_step", "t0_us": 30.0, "t1_us": 40.0,
              "args": {"forwards": 10, "tokens_emitted": 3}},
             {"name": "decode_step", "t0_us": 50.0, "t1_us": 60.0,
              "args": {"active": 2}}]
    view = {"spans": spans, "window_us": (0.0, 100.0)}
    params = run_mod.metric_spec("forwards_per_token.longgen")["params"]
    assert span_ratio.read(view, params, {}) == pytest.approx(650 / 515)
    # a program without the arguments (the parent) reads nothing
    assert span_ratio.read({"spans": spans[2:], "window_us": (0.0, 100.0)},
                           params, {}) is None


def test_paged_attn_roofline_counts_calls_from_the_events():
    full = full_config()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops.paged_read_min_seconds(full, 1500, 64, peaks)
    assert least == pytest.approx(1500 * 131072 / 819e9)
    view = {"kind": "serve", "config": full,
            "job": {"engine": {"page_tokens": 64}},
            "window_us": (0.0, 1e9), "capture_stretch_us": (100.0, 200.0),
            "spans": [{"name": "decode_step", "t0_us": 110.0, "t1_us": 150.0,
                       "args": {"kv_full_pages": 1000}},
                      {"name": "decode_step", "t0_us": 150.0, "t1_us": 190.0,
                       "args": {"kv_full_pages": 2000}},
                      {"name": "decode_step", "t0_us": 300.0, "t1_us": 390.0,
                       "args": {"kv_full_pages": 9}}],
            "tracks": {"0": [(0.0, 600.0, "paged_attn_decode.3")] * 70
                       + [(0.0, 5e3, "fusion.7")]}}
    params = run_mod.metric_spec("paged_attn_roofline.longgen")["params"]
    # 70 calls of 600 us: 42 ms of kernel, against 70 reads of 1500 pages
    assert paged_attn_roofline.read(view, params, peaks) \
        == pytest.approx(100 * 70 * least / 0.042)
    view["tracks"] = {"0": [(0.0, 5e3, "fusion.7")]}
    assert paged_attn_roofline.read(view, params, peaks) == 0.0
    assert paged_attn_roofline.read(dict(view, tracks=None), params,
                                    peaks) is None
