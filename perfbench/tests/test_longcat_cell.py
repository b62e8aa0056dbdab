"""The cell ``longcat-serve-reason-sat`` at rehearsal size on the CPU: the
line it prints in both trace modes, what has to come out as NOT correct
does (the fp8 control and the seven planted faults in the reference's
place, a token altered under the timed path), the program itself comes out
correct under limits as tight as the tiny size allows (in float32 it
serves the reference's own first choice: every gap is 0), the files state
the cut and the counts add up, the manifest keeps the rules of form, and
the two readers this cell brings read what they say."""

import argparse
import gzip
import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

os.environ.setdefault("PERFBENCH_REHEARSAL", "1")

from perfbench import run as run_mod                        # noqa: E402
from perfbench.lib import flops_longcatflash as flops       # noqa: E402
from perfbench.lib import manifest                          # noqa: E402
from perfbench.lib import reference_longcatflash as ref     # noqa: E402
from perfbench.lib import serve_longcat_entry as entry      # noqa: E402
from perfbench.readers import (flash_latent_roofline,       # noqa: E402
                               latent_attn_roofline, latent_scope_time,
                               scope_time_words, span_ratio)
from perfbench.tests import test_layers as tl               # noqa: E402

MAN = manifest.load()
CELL, CONFIG = "longcat-serve-reason-sat", "longcat-flash-omni-l4e16"
TIGHT = {"logit_gap_mean": 1e-5}
VARIANTS = [("fp8", None)] + [(None, f) for f in ref.FAULTS]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ctx_for(fault=None, seed=11):
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0, trace=0)
    ctx = run_mod.Ctx(ns, MAN)
    ctx.fault = fault
    ctx.traffic = dict(ctx.traffic, limits=TIGHT, check_requests=8)
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
    c = entry.compared(ctx, got["gaps"], res["failed"])
    assert correct(c), c
    assert c["logit_gap_mean"]["value"] == 0.0


@pytest.mark.parametrize("name", ["fp8"] + list(ref.FAULTS))
def test_control_and_planted_faults_are_not_correct(served, name):
    ctx, res, got = served
    c = entry.compared(ctx, got[name], res["failed"])
    assert not correct(c), c
    assert c["logit_gap_mean"]["value"] > 100 * TIGHT["logit_gap_mean"]


def test_the_witness_is_the_references_own_precision_and_no_fault():
    """``quant="bf16"``: read by the cell's ``limits`` tool beside the
    program. At the tiny size it serves other tokens than the float32
    reference at some positions, by far less than the fp8 control."""
    ctx = ctx_for()
    res = entry.window(ctx)
    got = entry.score(ctx, res["sample"], entry.WITNESS + (("fp8", None),))
    assert set(got) == {"gaps", "bf16", "fp8"}
    assert len(got["bf16"]) == len(got["gaps"])
    assert 0.0 <= got["bf16"].mean() < got["fp8"].mean() / 4


def test_a_token_altered_under_the_timed_path_is_not_correct():
    ctx = ctx_for(fault="token_altered")
    res = entry.run(ctx)
    assert not correct(res["compared"]), res["compared"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_run_prints_a_valid_line(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "perfbench/run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 7), "--seconds", "2",
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
        "serve_mfu.reason", "decode_dispatch_ms_p50.reason",
        "prefill_ms_p50.reason", "slot_occupancy_pct.reason",
        "moe_pairs_per_expert.reason", "moe_zero_share.reason"}
    assert want <= set(line["metrics"])
    if trace:
        # top-4 of 16 real and 8 identity experts: about a third
        assert 0.1 < line["metrics"]["moe_zero_share.reason"]["value"] < 0.6


def test_the_cells_files_state_the_cut_and_the_counts_add_up():
    full = full_config()
    entry_ = manifest.config_entry(MAN, CONFIG)
    assert full["reduced"] == entry_["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert full["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert full["source"] == entry_["source"]
    # every number of the catalog's row stands in the file under its key:
    # no width is cut
    for key, want in {
            "hidden_size": 6144, "ffn_hidden_size": 12288,
            "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
            "kv_lora_rank": 512, "q_lora_rank": 1536,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "qk_nope_head_dim": 128, "routed_scaling_factor": 6,
            "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
            "rope_theta": 10000000, "zero_expert_num": 256, "moe_topk": 12,
            "num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
            "n_routed_experts_routed": 512}.items():
        assert full[key] == want
    # the issue's arithmetic, a sublayer at a time
    p = flops.sublayer_params(full)
    assert p == {"mla": 90_570_752, "ffn": 226_492_416,
                 "expert": 37_748_736, "router": 4_718_592}
    assert 2 * (p["mla"] + p["ffn"]) + p["router"] == 638_844_928
    assert flops.n_params(full) == 5_172_749_312
    assert "5,172,749,312 parameters" in full["cut"]
    # 0.25 local pair a token a layer: 16 held of 768, top-12
    assert flops.matmul_params_per_token(full) == 4 * (
        638_844_928 + 0.25 * 37_748_736) + 6144 * 16384
    assert flops.keys_seen(0, 7) == 36 and flops.keys_seen(5, 5) == 6
    # a decoded token's attention: two sublayers a layer, 192 + 128 a head
    one = flops.forward_flops(full, 1, 1001) \
        - 2 * flops.matmul_params_per_token(full)
    assert one == 2 * 8 * 1001 * 64 * 320
    # the cache: 576 values a token a sublayer published, 640 stored
    mc = entry.model_config(ctx_for())
    assert (mc.n_experts, mc.n_experts_held, mc.n_zero_experts) == (16, 4, 8)
    from tpudist.config import ModelConfig
    wide = ModelConfig(name="longcatflash", d_model=6144, n_heads=64,
                       d_ff=2048, d_ff_dense=12288, q_lora_rank=1536,
                       kv_lora_rank=512, qk_nope_head_dim=128,
                       qk_rope_head_dim=64, v_head_dim=128)
    assert wide.latent_row == 640
    mix = json.load(open(os.path.join(manifest.BENCH_DIR, "traffic",
                                      "reason-sat.json")))
    e = mix["engine"]
    assert (e["slots"], e["page_tokens"], e["pages"], e["prompt_pad"],
            e["max_seq"], e["decode_k"]) == (192, 64, 4096, 1024, 2048, 8)
    assert mix["rate_rps"] == pytest.approx(1.25 * mix["knee_rps"])
    assert mix["entry"] == "serve_longcat"


def test_what_this_cell_adds_to_the_manifest_keeps_the_rules_of_form():
    # the driver refuses the whole file for one line over 200 characters
    cfg = manifest.config_entry(MAN, CONFIG)
    row = next(w for w in MAN["workloads"] if w["name"] == CELL)
    for line in (cfg["why"], cfg["source"], row["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable()
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(row) == {"name", "config", "traffic", "chips", "why"}
    assert row["chips"] == 1 and MAN["configs"][-1] is cfg \
        and MAN["workloads"][-1] is row
    mine = [m for m in MAN["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 22 and MAN["per_layer"][-22:] == mine
    for m in mine:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "serve_tokens_per_s" \
            and m["moves"] in manifest.end_to_end(MAN, CELL)
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "metrics", m["name"] + ".json"))
    # the accepted share of the whole step's peak is reported here too
    assert "serve_mfu.reason" in {m["name"] for m in mine}
    assert len(json.dumps(MAN, indent=1)) < 64 * 1024


def test_the_metric_files_scope_words_are_the_programs():
    from tpudist import scopes
    words = {w for s in scopes.MODEL_SCOPES + scopes.BLOCK_SCOPES
             + scopes.LATENT_SCOPES for w in s.split("/")} \
        - {w for s in scopes.SCOPES for w in s.split("/")}
    seen = 0
    for m in MAN["per_layer"]:
        spec = run_mod.metric_spec(m["name"])
        if spec["reader"] == "latent_scope_time":
            assert set(spec["params"]["words"]) == words
            assert m["workloads"] == [CELL]
            # the pattern names scopes the program can enter
            assert re.match(r"\^(decode|prefill)/", spec["params"]["scope"])
            for name in re.findall(r"latent_\w+|zero", spec["params"]["scope"]):
                assert any(s.endswith(name) for s in scopes.LATENT_SCOPES)
            seen += 1
    assert seen == 7


# ------------------------------------------------------------- readers


def test_latent_attn_roofline_counts_calls_from_the_events():
    full = full_config()
    least = flops.latent_read_min_seconds(full, 3000, 64, PEAKS)
    # each mapped page's rows once at the published 576 values x 2 B
    assert least["bytes"] == 3000 * 64 * 576 * 2
    assert least["flops"] == 3000 * 64 * 2 * 64 * (576 + 512)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(3000 * 73728 / 819e9)
    view = {"kind": "serve", "config": full,
            "job": {"engine": {"page_tokens": 64}},
            "window_us": (0.0, 1e9), "capture_stretch_us": (100.0, 200.0),
            "spans": [{"name": "decode_step", "t0_us": 110.0, "t1_us": 150.0,
                       "args": {"kv_full_pages": 2000}},
                      {"name": "decode_step", "t0_us": 150.0, "t1_us": 190.0,
                       "args": {"kv_full_pages": 4000}},
                      {"name": "decode_step", "t0_us": 300.0, "t1_us": 390.0,
                       "args": {"kv_full_pages": 9}}],
            "tracks": {"0": [(0.0, 600.0, "paged_attn_decode.3")] * 64
                       + [(0.0, 5e3, "fusion.7")]}}
    params = run_mod.metric_spec("latent_attn_roofline.reason")["params"]
    # 64 calls of 600 us: 38.4 ms of kernel, against 64 reads of 3000 pages
    got = latent_attn_roofline.read(view, params, PEAKS)
    assert got == pytest.approx(100 * 64 * least["seconds"] / 0.0384)
    assert got < 100
    view["tracks"] = {"0": [(0.0, 5e3, "fusion.7")]}
    assert latent_attn_roofline.read(view, params, PEAKS) == 0.0
    assert latent_attn_roofline.read(dict(view, tracks=None), params,
                                     PEAKS) is None
    # a program whose spans lack the argument (the parent): nothing to read
    view["tracks"] = {"0": [(0.0, 600.0, "paged_attn_decode.3")]}
    for s in view["spans"]:
        s["args"] = {}
    assert latent_attn_roofline.read(view, params, PEAKS) is None


def test_flash_latent_roofline_counts_prompts_from_the_events():
    full = full_config()
    least = flops.flash_prefill_min_seconds(full, 1024, PEAKS)
    # 8 sublayers, 524,800 keys seen, 64 heads x (192 + 128) a key
    assert least["flops"] == 8 * 2 * flops.keys_seen(0, 1023) * 64 * 320
    assert least["bytes"] == 8 * 2 * 1024 * 64 * (2 * 192 + 2 * 128)
    assert least["bound"] == "flops"
    view = {"kind": "serve", "config": full,
            "job": {"engine": {"prompt_pad": 1024}},
            "tracks": {"0": [(0.0, 400.0, "flash_fwd.1")] * 24
                       + [(0.0, 5e3, "fusion.7")]}}
    params = run_mod.metric_spec("flash_latent_roofline.reason")["params"]
    # 24 events of 0.4 ms: three prompts of eight sublayers, 9.6 ms
    got = flash_latent_roofline.read(view, params, PEAKS)
    assert got == pytest.approx(100 * 3 * least["seconds"] / 0.0096)
    assert got < 100
    view["tracks"] = {"0": [(0.0, 5e3, "fusion.7")]}
    assert flash_latent_roofline.read(view, params, PEAKS) == 0.0
    assert flash_latent_roofline.read(dict(view, tracks=None), params,
                                      PEAKS) is None


def test_span_ratio_reads_the_identity_experts_share():
    spans = [{"name": "decode_step", "t0_us": 10.0, "t1_us": 20.0,
              "args": {"moe_pairs_zero": 700.0, "moe_pairs_all": 2304.0}},
             {"name": "decode_step", "t0_us": 30.0, "t1_us": 40.0,
              "args": {"moe_pairs_zero": 800.0, "moe_pairs_all": 2196.0}},
             {"name": "decode_step", "t0_us": 50.0, "t1_us": 60.0,
              "args": {"active": 2}}]
    view = {"spans": spans, "window_us": (0.0, 100.0)}
    params = run_mod.metric_spec("moe_zero_share.reason")["params"]
    assert span_ratio.read(view, params, {}) == pytest.approx(1500 / 4500)
    assert span_ratio.read({"spans": spans[2:], "window_us": (0.0, 100.0)},
                           params, {}) is None


def test_latent_scope_time_reads_the_scopes_the_other_lists_drop(tmp_path):
    doc = tl.doc()
    for e in doc["traceEvents"]:
        t = (e.get("args") or {}).get("tf_op", "")
        if t.endswith("ffn/dot_general:"):
            e["args"]["tf_op"] = t.replace("ffn/", "moe/zero/")
        elif t.endswith("attn/kv_write/scatter:"):
            e["args"]["tf_op"] = t.replace("kv_write/", "latent_q/")
    d = tmp_path / "capture"
    d.mkdir()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(doc, f)
    from perfbench.lib import capture
    tracks = capture.load_tracks(str(d))
    view = {"kind": "serve", "capture_dir": str(d), "tracks": tracks,
            "capture": capture.reduce_tracks(tracks), "spans": [],
            "job": {"capture_dispatches": 2}}
    experts = run_mod.metric_spec("decode_scope_ms.experts.reason")
    latent = run_mod.metric_spec("decode_scope_ms.latent.reason")
    assert experts["reader"] == latent["reader"] == "latent_scope_time"
    assert latent_scope_time.read(view, experts["params"], {}) \
        == pytest.approx(0.100)
    assert latent_scope_time.read(view, latent["params"], {}) \
        == pytest.approx(0.100)
    # the word list of the ``cohere2moe`` cell does not know these scopes
    docqa = run_mod.metric_spec("decode_scope_ms.experts.docqa")["params"]
    assert scope_time_words.read(
        view, dict(experts["params"], words=docqa["words"]), {}) == 0.0
    assert "layers" not in view
