"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, with
``--trace 0`` and ``--trace 1``: the command, the data files, the entries,
the readers, the capture reduction and the last line's shape, as the
driver will read it. Each run is a process of its own, as on the chip (a
four-chip cell would get four virtual CPU devices). No number here is a
device number: the line names the platform it ran on."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import manifest

ROOT = manifest.ROOT
MAN = manifest.load()
CELLS = [(w["name"], w["chips"]) for w in MAN["workloads"]]


def run_cell(cell, chips, trace, seed=2147483659, seconds=3, env_extra=None):
    env = dict(os.environ, PERFBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu",
               TF_CPP_MIN_LOG_LEVEL="3", BENCH_RUN="ignored",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.update(env_extra or {})
    cmd = [sys.executable] + MAN["command"][1:] + [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_cell_prints_a_line_the_validator_passes(cell, chips, trace):
    p = run_cell(cell, chips, trace)
    assert p.returncode == 0, p.stderr[-3000:]
    last = p.stdout.rstrip("\n").rsplit("\n", 1)[-1]
    line = json.loads(last)
    lack = ("flash_attn_roofline",) if trace else ()
    assert manifest.validate_line(line, MAN, cell, trace, lack) == []
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "setup_s" in line["metrics"] or trace
    if trace:
        d = line["device"]
        assert 0 < d["busy_s"] <= d["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
    # the compared numbers, each beside its limit, end stderr too
    assert "perfbench: compared" in p.stderr.rstrip().rsplit("\n", 1)[-1]


def test_no_accelerator_is_an_error_and_prints_no_line():
    cell, chips = CELLS[0]
    p = run_cell(cell, chips, 0, env_extra={"PERFBENCH_REHEARSAL": ""})
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")
