"""HBM watermark sampler: "the staging budget was nearly blown" as a
number, not a guess.

A background thread polls ``device.memory_stats()`` every ``period_s``
and keeps the high-water mark across the run. The poll is a host-side
runtime query — it enqueues no device work, so sampling cannot perturb
the training it observes.

What the TPU runtime reports (measured on a v5e, libtpu 0.0.34):
``bytes_in_use`` / ``peak_bytes_in_use`` cover live buffers and program
code only; the scratch of a loaded executable is held apart, under
``bytes_reserved`` / ``peak_bytes_reserved`` — exactly the executable's
``memory_analysis().temp_size_in_bytes``, reserved from its first run
until it is dropped. A device's footprint is the SUM of the two, and
that sum is what the watermark tracks (live buffers alone read 2.8 GB
on a flagship train step whose scratch is 12.5 GB).

Backends that report no memory stats at all (the CPU test mesh) fall
back to the process's peak RSS (``ru_maxrss``) so the watermark fields
are always populated: on the CPU backend device memory IS host memory,
and the `hbm_source` field says which estimate you are reading.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional


def _rss_peak_bytes() -> Optional[int]:
    """Peak RSS of this process in bytes (Linux ru_maxrss is KiB)."""
    try:
        import resource
        import sys
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(ru) if sys.platform == "darwin" else int(ru) * 1024
    except Exception:
        return None


class HbmSampler:
    """Background high-water-mark tracker over local devices.

    ``period_s > 0`` starts a daemon thread; ``period_s == 0`` makes the
    sampler manual (callers invoke :meth:`sample` themselves — the bench
    sweeps do this so the sampling points bracket their timed windows).
    One synchronous sample is always taken at construction so short runs
    still report a watermark.
    """

    def __init__(self, period_s: float = 2.0):
        if period_s < 0:
            raise ValueError(f"period_s must be >= 0, got {period_s}")
        self.period_s = float(period_s)
        self.peak_in_use = 0        # max over time of max over devices
        self.last_in_use = 0
        self.last_reserved: Optional[int] = None  # allocator reservation
        self.peak_per_device: list = []  # watermark per local device
        self.limit_bytes: Optional[int] = None
        self.source = "none"        # memory_stats | rss | none
        self.samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.sample()
        if self.period_s > 0:
            self._thread = threading.Thread(
                target=self._loop, name="tpudist-hbm", daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            self.sample()

    def sample(self) -> None:
        """One poll of every local device; fold into the high-water
        mark. Never raises — a dead backend must not kill the thread."""
        in_use = 0
        reserved = None
        got_stats = False
        per_device = []
        try:
            import jax
            for d in jax.local_devices():
                try:
                    stats = d.memory_stats()
                except Exception:
                    stats = None
                if not stats:
                    continue
                got_stats = True
                dev_in_use = int(stats.get("bytes_in_use", 0))
                dev_res = int(stats.get("bytes_reserved", 0))
                # live buffers + executable scratch, now and at their
                # reported peaks (the reservation outlives each run, so
                # the peaks coincide)
                per_device.append(max(
                    dev_in_use + dev_res,
                    int(stats.get("peak_bytes_in_use", 0))
                    + int(stats.get("peak_bytes_reserved", dev_res))))
                in_use = max(in_use, dev_in_use)
                if "bytes_reserved" in stats:
                    reserved = max(reserved or 0, dev_res)
                limit = stats.get("bytes_limit")
                if limit:
                    self.limit_bytes = int(limit)
        except Exception:
            pass
        if got_stats:
            self.source = "memory_stats"
            self.last_in_use = in_use
            if len(per_device) == len(self.peak_per_device):
                per_device = [max(a, b) for a, b in
                              zip(per_device, self.peak_per_device)]
            self.peak_per_device = per_device
            self.last_reserved = reserved
            self.peak_in_use = max(self.peak_in_use, *per_device)
        elif self.source != "memory_stats":
            # RSS fallback ONLY on backends that never reported device
            # stats: one transient memory_stats() failure mid-run must
            # not fold host RSS (tens of GB on a TPU VM) into a device
            # watermark that can never recede
            rss = _rss_peak_bytes()
            if rss is not None:
                self.source = "rss"
                self.last_in_use = rss
                self.peak_in_use = max(self.peak_in_use, rss)
        self.samples += 1

    def split(self) -> Dict[str, Any]:
        """Watermark fields for the ``kind=timing`` record and the
        flight-record dump."""
        frac = None
        if self.limit_bytes and self.peak_in_use:
            frac = round(self.peak_in_use / self.limit_bytes, 4)
        return {"hbm_peak_bytes": self.peak_in_use or None,
                "hbm_bytes_in_use": self.last_in_use or None,
                "hbm_peak_bytes_per_device": self.peak_per_device or None,
                "hbm_bytes_reserved": self.last_reserved,
                "hbm_limit_bytes": self.limit_bytes,
                "hbm_peak_fraction": frac,
                "hbm_source": self.source}

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self.sample()   # final watermark covers the run's tail
