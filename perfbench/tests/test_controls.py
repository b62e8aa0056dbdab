"""What has to come out as NOT correct does: the control (the reference in
the nearest precision below the one the configurations state, fp8, put in
the program's place) and each fault a cell can have, planted under the
timed path while the rest of a run is driven as it stands (the look for a
chip skipped). Tiny sizes on the CPU; the limits here are the tiny sizes'
own (the program in float32 agrees with the reference to 1e-5)."""

import argparse
import os
import tempfile

import numpy as np
import pytest

os.environ.setdefault("PERFBENCH_REHEARSAL", "1")

from perfbench import run as run_mod                        # noqa: E402
from perfbench.lib import manifest, reference               # noqa: E402
from perfbench.lib import serve_entry, train_entry          # noqa: E402

MAN = manifest.load()
TIGHT = {"loss_gap_step1": 3e-4, "loss_gap_step2": 3e-4,
         "loss_gap_step3": 3e-4, "grad_norm_gap": 2e-3,
         "update_norm_gap": 2e-3}


def ctx_for(cell, seed=11, seconds=2.0, fault=None):
    ns = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                            trace=0)
    ctx = run_mod.Ctx(ns, MAN)
    ctx.fault = fault
    ctx.arm_compile_count = lambda on: None
    ctx.workdir = tempfile.mkdtemp(prefix="perfbench-test-")
    return ctx


def correct(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())


@pytest.fixture(scope="module")
def train_first():
    ctx = ctx_for("mistral7b-train-s4096")
    ctx.traffic = dict(ctx.traffic, dtype="float32")
    first, lr, rows = train_entry.first_steps(ctx)
    ref = reference.train_steps(ctx.seed, ctx.config, rows, lr)
    return ctx, first, lr, ref, rows


def test_train_program_agrees_with_the_reference(train_first):
    _, first, _, ref, rows = train_first
    got = train_entry.compare(first, ref, TIGHT, rows)
    assert got["rows_mismatch"]["value"] == 0 and correct(got)


def test_rows_the_program_staged_wrongly_are_not_correct(train_first):
    """The rows are the benchmark's own draw: a feed that repeats a row
    where the stream has another is seen before any loss is."""
    _, first, _, ref, rows = train_first
    fed = dict(first, tokens=first["tokens"].copy())
    fed["tokens"][2, 1] = fed["tokens"][2, 0]
    got = train_entry.compare(fed, ref, TIGHT, rows)
    assert got["rows_mismatch"]["value"] == 1 and not correct(got)


@pytest.mark.parametrize("kw", [
    {"mode": "fp8"},                    # the control
    {"rows": [0, 1]},                   # half of the batch left out
    {"frozen": True},                   # a step that returns its state
], ids=["control_fp8", "half_batch", "state_unchanged"])
def test_train_control_and_faults_in_the_references_place_fail(train_first,
                                                               kw):
    ctx, _, lr, ref, rows = train_first
    alt = reference.train_steps(ctx.seed, ctx.config, rows, lr, **kw)
    assert not correct(train_entry.compare(alt, ref, TIGHT))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_run_with_the_timed_path_broken_is_not_correct(fault):
    """The whole of a run but the look for a chip, the compiled step broken
    underneath (through the probe that stands around it)."""
    ctx = ctx_for("mistral7b-train-s4096", fault=fault)
    ctx.traffic = dict(ctx.traffic, dtype="float32", limits=TIGHT)
    res = train_entry.run(ctx)
    assert not correct(res["compared"])


def test_serve_run_is_correct_and_a_token_altered_is_not():
    ctx = ctx_for("internlm2-serve-open", seconds=3.0)
    ctx.traffic = dict(ctx.traffic, limits={"logit_gap_max": 1e-3})
    assert correct(serve_entry.run(ctx)["compared"])
    ctx = ctx_for("internlm2-serve-open", seconds=3.0, fault="token_altered")
    ctx.traffic = dict(ctx.traffic, limits={"logit_gap_max": 1e-3})
    assert not correct(serve_entry.run(ctx)["compared"])


def test_serve_control_in_fp8_reads_a_gap():
    ctx = ctx_for("internlm2-serve-open", seconds=3.0)
    res = serve_entry.window(ctx)
    got = serve_entry.score(ctx, res["sample"], "fp8")
    assert got["gaps"].max() <= 1e-3
    assert got["control_gaps"].max() > 10 * max(got["gaps"].max(), 1e-3)
