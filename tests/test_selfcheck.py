"""Contract tests for the on-chip acceptance lane (tpudist.selfcheck).

The checks themselves are hardware tests (run on a TPU host, or via the
launcher gate — tests/test_launcher.py covers the wiring); what the CPU
lane can pin is the module's contract: the off-TPU refusal (the lane
must never silently pass by interpreting kernels on CPU) and the check
registry's integrity.
"""

import os
import subprocess
import sys

from tpudist import selfcheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_off_tpu():
    """Backend != tpu exits 2 — distinct from a check failure (1) — and
    does not run any check."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.selfcheck"],
        cwd=REPO, env=env,
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "refusing" in r.stdout
    assert "PASS" not in r.stdout and "FAIL" not in r.stdout


def test_check_registry_covers_both_kernels_and_both_models():
    names = [fn.__name__ for fn in selfcheck.CHECKS]
    assert len(names) == len(set(names))
    joined = " ".join(names)
    # the load-bearing coverage: both pallas kernels (incl. the multi-block
    # long-context schedule and GQA), a train smoke per model family, and
    # the forced-stall flight-recorder drill (CI's observability gate)
    for needle in ("fused_xent", "flash_attention", "long_context", "gqa",
                   "train_step", "moe", "flight_recorder", "autotune",
                   "devtime", "chaos"):
        assert needle in joined, f"selfcheck lane lost its {needle} check"
