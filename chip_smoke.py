#!/usr/bin/env python3
"""Chip smoke: the train and serve CLIs, end to end, on the TPU.

    python3 chip_smoke.py

Runs the system's two entry points (``python -m tpudist.train``,
``python -m tpudist.serve``) at the full width of the flagship model
(``config.flagship_model_config``: 4 layers, d_model 2048, 16 heads x 128,
d_ff 5504, vocab 32000; weights random from a seed) as child processes,
ONE AFTER ANOTHER, and asserts on the artifacts each run leaves — an exit
code alone would pass with the device hidden behind every advisory
fallback the program has. This parent never imports jax: a process that
has touched jax holds the chip, and the children need it.

Phases (each a separate process, all sharing one persistent compile
cache — ``JAX_COMPILATION_CACHE_DIR`` if set, else the program's own
``<checkout>/.jax_cache``):

  A  train, cold: 2 epochs of 12 steps through the superstep path
  B  the same, warm, with a device trace (--profile-window 2)
  C  the fused LM head + remat (the flagship default picks the plain head)
  D  serve through the paged engine under Poisson arrivals
  E  fault injection must go red (exit 1, verdict "fail")
  F  four chips only: data=4, then fsdp=2 x tensor=2

Exits non-zero at the first failed phase. On success the last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as jax
reports it. There is no CPU mode: without a TPU it fails at once.

Checkpoints and profiler captures go to ``.chip_smoke/`` (git-ignored,
removed on exit); only small summaries go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
BUDGET_S = 1150.0     # the contract allows 1200 s, compilation included
T0 = time.monotonic()

FLAGSHIP = ["--vocab-size", "32000", "--n-layers", "4", "--d-model", "2048",
            "--n-heads", "16", "--n-kv-heads", "16", "--d-ff", "5504"]
TRAIN = [sys.executable, "-m", "tpudist.train", "--model", "transformer",
         *FLAGSHIP, "--seq-len", "512", "--dtype", "bfloat16"]
# the fields of the run-end records the phases are judged on (and print)
TIMING_FACTS = ("device_kind", "device_count", "steps_per_dispatch",
                "program_traces", "mosaic_kernels", "compile_warmup_s",
                "compile_cache_hits", "compile_cache_misses",
                "run_s", "steps", "mfu", "peak_tflops", "hbm_source",
                "hbm_limit_bytes", "hbm_peak_bytes",
                "hbm_peak_bytes_per_device", "collective_ops",
                "ici_bytes_per_step", "trace_status")
LEDGER_FACTS = ("total_hbm_bytes", "exact", "program_temp_complete",
                "params_bytes", "opt_state_bytes", "slabs_bytes",
                "kv_pool_bytes", "program_temp_bytes", "headroom_bytes")
SERVE_FACTS = ("status", "requests", "completed", "generated_tokens",
               "wall_s", "prefill_compiles", "decode_compiles",
               "verify_compiles", "kv_page_tokens", "kv_pages_total",
               "kv_pages_used_peak", "ttft_p99_s", "itl_p99_s",
               "tokens_per_sec_per_chip")
PROBE = ("import json, jax; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))")


class PhaseFailed(Exception):
    pass


def run(phases: list, name: str, cmd: list, *, expect_rc: int = 0,
        verdict: str | None = None) -> dict:
    """One child process through a CLI entry point, run to its end (or
    killed, with its whole process group, when the smoke's time budget
    runs out). Appends the phase's record to ``phases``."""
    save_dir = os.path.join(WORK, name)
    os.makedirs(save_dir, exist_ok=True)
    verdict_path = os.path.join(save_dir, "job_status.txt")
    cmd = [*cmd, "--save-dir", save_dir]
    env = {**os.environ, "TPUDIST_VERDICT_PATH": verdict_path}
    log_path = os.path.join(OUT, f"{name}.log")
    left = BUDGET_S - (time.monotonic() - T0)
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=max(left, 1.0))
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the child is its own group leader: whatever it started
            # (checkpoint writers, profiler servers) goes with it
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    wall = time.monotonic() - t0
    phase = {"phase": name, "cmd": " ".join(cmd[1:]), "rc": rc,
             "wall_s": round(wall, 1), "save_dir": save_dir}
    phases.append(phase)
    print(f"[{name}] rc={rc} wall={wall:.1f}s  {phase['cmd']}", flush=True)
    got_verdict = None
    if os.path.exists(verdict_path):
        with open(verdict_path) as f:
            got_verdict = f.read().strip()
    phase["verdict"] = got_verdict
    problems = []
    if rc is None:
        problems.append(f"timed out: the {BUDGET_S:.0f}s budget ran out")
    elif rc != expect_rc:
        problems.append(f"exit code {rc}, expected {expect_rc}")
    if verdict is not None and got_verdict != verdict:
        problems.append(f"verdict {got_verdict!r}, expected {verdict!r}")
    if problems:
        with open(log_path) as f:
            tail = f.read()[-6000:]
        print(f"[{name}] ---- end of output ----\n{tail}", flush=True)
        fail(phase, problems)
    return phase


def fail(phase: dict, problems: list) -> None:
    phase["problems"] = problems
    for p in problems:
        print(f"[{phase['phase']}] FAILED: {p}", flush=True)
    raise PhaseFailed(phase["phase"])


def records(phase: dict) -> dict:
    """metrics.jsonl of a phase, grouped by ``kind``. The file is small
    (one line per logged step group, epoch and run-end record) and is
    copied next to the summaries."""
    src = os.path.join(phase["save_dir"], "metrics.jsonl")
    shutil.copyfile(src, os.path.join(OUT, f"{phase['phase']}.metrics.jsonl"))
    by_kind: dict = {}
    with open(src) as f:
        for line in f:
            rec = json.loads(line)
            by_kind.setdefault(rec.get("kind"), []).append(rec)
    return by_kind


def check(phase: dict, facts: dict, checks: list) -> None:
    """Print the facts a phase was judged on; fail on any false check.
    The phase's checkpoints (> 3 GB an epoch at this width) go first: the
    next phase needs the disk."""
    shutil.rmtree(phase["save_dir"], ignore_errors=True)
    phase["facts"] = facts
    print(f"[{phase['phase']}] " + json.dumps(facts), flush=True)
    problems = [msg for ok, msg in checks if not ok]
    if problems:
        fail(phase, problems)


def is_num(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def last(rec: dict, kind: str) -> dict:
    return (rec.get(kind) or [{}])[-1]


def ledger_facts(rec: dict) -> dict:
    ledger = last(rec, "memledger")
    return {f"ledger_{k}": ledger.get(k) for k in LEDGER_FACTS}


def train_facts(phase: dict, device: dict) -> tuple:
    """The facts and checks every flagship train phase shares: the run
    was on the device, the advisory planes were not degraded, and the
    loss went down."""
    rec = records(phase)
    timing = last(rec, "timing")
    epochs = [e["avg_loss"] for e in rec.get("epoch", [])]
    steps = [s["loss"] for s in rec.get("step", [])]
    facts = {"epoch_avg_loss": epochs, "step_losses": len(steps),
             **{k: timing.get(k) for k in TIMING_FACTS},
             **ledger_facts(rec)}
    kernels = facts["mosaic_kernels"] = facts["mosaic_kernels"] or []
    checks = [
        (timing.get("platform") == "tpu"
         and timing.get("device_kind") == device["kind"]
         and timing.get("device_count") == device["count"],
         f"the run's device {timing.get('platform')!r}/"
         f"{timing.get('device_kind')!r} x{timing.get('device_count')} is "
         f"not the probed {device}"),
        (bool(epochs) and all(is_num(x) for x in epochs + steps),
         f"losses not all finite: epochs {epochs}, steps {steps}"),
        (len(epochs) < 2 or epochs[-1] < epochs[0],
         f"loss did not go down across epochs: {epochs}"),
        (facts["program_traces"] == 1,
         f"train program traced {facts['program_traces']} times, not once"),
        (any(k.startswith("flash_") for k in kernels),
         f"no Mosaic flash kernel in the compiled step (kernels: "
         f"{kernels}): attention gave way to the XLA path"),
        (is_num(facts["mfu"]) and facts["mfu"] > 0,
         f"mfu is {facts['mfu']!r}, not a number"),
        (facts["hbm_source"] == "memory_stats",
         f"hbm_source is {facts['hbm_source']!r}, not memory_stats"),
        (is_num(facts["hbm_limit_bytes"])
         and facts["ledger_total_hbm_bytes"] == facts["hbm_limit_bytes"]
         and facts["ledger_total_hbm_bytes"] != 16_000_000_000,
         f"memledger total {facts['ledger_total_hbm_bytes']} is not the "
         f"device's bytes_limit {facts['hbm_limit_bytes']}"),
        (facts["ledger_program_temp_complete"] is True,
         "memledger skipped, or a program gave no memory analysis"),
        (facts["trace_status"] == "success",
         f"trace_status {facts['trace_status']!r}"),
    ]
    if device["count"] == 1:
        # on four chips the watermark does not reconcile yet (PERF.md,
        # open questions): 4.4 % unattributed on data=4, and device 0
        # carries init_state's unsharded transient on sharded layouts
        checks.append((facts["ledger_exact"] is True,
                       "memledger not exact: the watermark and the derived "
                       "footprint differ by more than 1 % of HBM"))
    if "v5 lite" in device["kind"].lower() or "v5e" in device["kind"].lower():
        checks.append((facts["peak_tflops"] == 197.0,
                       f"peak_tflops {facts['peak_tflops']} on a v5e"))
    return rec, facts, checks


def main() -> int:
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT, exist_ok=True)
    phases: list = []
    summary = {"ok": False, "phases": phases}
    try:
        probe = subprocess.run([sys.executable, "-c", PROBE], cwd=HERE,
                               capture_output=True, text=True, timeout=300)
        if probe.returncode != 0:
            print(probe.stdout + probe.stderr, flush=True)
            print("chip_smoke: jax could not list its devices", flush=True)
            return 1
        device = json.loads(probe.stdout.strip().splitlines()[-1])
        summary["device"] = device
        if device["platform"] != "tpu":
            print(f"chip_smoke: platform is {device['platform']!r} "
                  f"({device['kind']}), not tpu; this smoke has no CPU mode",
                  flush=True)
            return 1
        print(f"chip_smoke: {device['count']} x {device['kind']}", flush=True)
        smoke(device, phases)
        summary["ok"] = True
    except PhaseFailed as e:
        print(f"chip_smoke: phase {e} failed", flush=True)
    finally:
        summary["wall_s"] = round(time.monotonic() - T0, 1)
        with open(os.path.join(OUT, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        shutil.rmtree(WORK, ignore_errors=True)
    if not summary["ok"]:
        return 1
    print(f"chip_smoke: all {len(phases)} phases passed in "
          f"{summary['wall_s']}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def smoke(device: dict, phases: list) -> None:
    # the BENCH shape (56 sequences of 512 a step), 12 steps an epoch;
    # --log-every 4 resolves the default superstep length to k=4, three
    # dispatches an epoch
    flagship = [*TRAIN, "--train-batch-size", "56", "--n-samples", "672",
                "--epochs", "2", "--log-every", "4"]

    # ---- A: train, cold
    a = run(phases, "A_train_cold", flagship, verdict="success")
    _, facts_a, checks = train_facts(a, device)
    check(a, facts_a, checks)

    # ---- B: the same program from the compile cache, device trace on
    b = run(phases, "B_train_warm_traced",
            [*flagship, "--profile-window", "2"], verdict="success")
    rec, facts, checks = train_facts(b, device)
    dev = last(rec, "devtime")
    facts.update(phase_a_compile_warmup_s=facts_a["compile_warmup_s"],
                 devtime_devices=dev.get("devices"),
                 devtime_compute_s=dev.get("compute_s"),
                 devtime_window_s=dev.get("window_s"),
                 comm_status=dev.get("comm_status"))
    # phase A compiled (or, on a rerun, loaded) every program B needs: B
    # must load them all. The two compile+warmup times are printed with
    # the counts; a ratio of them would fail the rerun, where A is warm too
    check(b, facts, checks + [
        (facts["compile_cache_misses"] == 0
         and (facts["compile_cache_hits"] or 0) > 0,
         f"no persistent-cache hit: {facts['compile_cache_hits']} hit(s), "
         f"{facts['compile_cache_misses']} miss(es); compile+warmup "
         f"{facts['compile_warmup_s']}s against phase A's "
         f"{facts_a['compile_warmup_s']}s"),
        (bool(dev) and (dev.get("devices") or 0) >= 1
         and (dev.get("compute_s") or 0) > 0,
         f"no kind=devtime record with a device track: {dev or None}"),
        (dev.get("comm_status") != "fail",
         f"devtime comm_status {dev.get('comm_status')!r}"),
    ])

    # ---- C: the fused LM head (``--lm-head fused``)
    c = run(phases, "C_train_fused_head",
            [*TRAIN, "--lm-head", "fused", "--remat", "--train-batch-size",
             "96", "--n-samples", "384", "--epochs", "1", "--log-every",
             "2"], verdict="success")
    _, facts, checks = train_facts(c, device)
    check(c, facts, checks + [
        ({"fused_xent_fwd", "fused_xent_bwd"} <= set(facts["mosaic_kernels"]),
         f"fused LM-head kernels not in the compiled step: "
         f"{facts['mosaic_kernels']}"),
    ])

    # ---- D: serve, paged engine, open-loop arrivals
    n_req = 16
    d = run(phases, "D_serve_paged",
            [sys.executable, "-m", "tpudist.serve", "--model", "transformer",
             *FLAGSHIP, "--slots", "8", "--max-seq", "1024",
             "--prompt-pad", "256", "--kv-page-tokens", "64",
             "--requests", str(n_req), "--request-rate", "4",
             "--max-new-tokens", "32"])
    rec = records(d)
    serve = last(rec, "serve")
    facts = {**{k: serve.get(k) for k in SERVE_FACTS}, **ledger_facts(rec)}
    check(d, facts, [
        (facts["completed"] == n_req and facts["requests"] == n_req,
         f"{facts['completed']}/{n_req} requests completed"),
        ((facts["prefill_compiles"], facts["decode_compiles"]) == (1, 1),
         f"program pin broken: {facts['prefill_compiles']} prefill / "
         f"{facts['decode_compiles']} decode compiles"),
        ((facts["generated_tokens"] or 0) >= n_req,
         f"generated {facts['generated_tokens']} tokens"),
        (facts["kv_page_tokens"] == 64, "the paged engine did not run"),
        (facts["ledger_exact"] is True
         and facts["ledger_program_temp_complete"] is True
         and facts["ledger_total_hbm_bytes"] == facts_a["hbm_limit_bytes"],
         "serve memledger missing, skipped or not exact"),
    ])

    # ---- E: red must be red (the default MLP workload)
    run(phases, "E_fail_at",
        [sys.executable, "-m", "tpudist.train", "--epochs", "2",
         "--fail-at", "1"], expect_rc=1, verdict="fail")

    # ---- F: four chips — the shard_map + explicit psum path (data=4,
    # 56 sequences a chip), then jit + shardings (fsdp=2 x tensor=2, a
    # quarter of the state a chip)
    if device["count"] < 4:
        print(f"[F] skipped: {device['count']} device(s), needs 4",
              flush=True)
        return
    params_a = facts_a["ledger_params_bytes"]
    for name, layout, batch, share in (
            ("F_data4", ["--data", "4"], 224, 1.0),
            ("F_fsdp2_tensor2", ["--fsdp", "2", "--tensor", "2"], 112, 0.25)):
        f = run(phases, name,
                [*TRAIN, "--train-batch-size", str(batch), "--n-samples",
                 str(8 * batch), "--epochs", "1", "--log-every", "4",
                 *layout], verdict="success")
        _, facts, checks = train_facts(f, device)
        per_dev = facts["hbm_peak_bytes_per_device"] or []
        params = facts["ledger_params_bytes"] or 0
        checks += [
            (len(per_dev) == device["count"] and min(per_dev) > 0
             and max(per_dev) < 1.5 * min(per_dev),
             f"not every device carried its share: peak bytes {per_dev}"),
            (0.9 * share * params_a < params < 1.1 * share * params_a,
             f"per-device params {params} B are not {share:g} of the "
             f"one-chip {params_a} B"),
        ]
        if name == "F_data4":
            # the gradient all-reduce this path writes itself; under jit +
            # shardings the partitioner inserts the collectives at compile
            # time and the lowered text the run parses holds none
            checks.append((
                (facts["collective_ops"] or 0) >= 1
                and (facts["ici_bytes_per_step"] or 0) > 0,
                f"no collective in the lowered step: "
                f"{facts['collective_ops']} op(s), "
                f"{facts['ici_bytes_per_step']} B over ICI"))
        check(f, facts, checks)


if __name__ == "__main__":
    sys.exit(main())
