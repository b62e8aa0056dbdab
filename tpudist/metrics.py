"""Observability: step timing, throughput, rank-0 structured logging.

The reference had NO timing at all (SURVEY.md §5.1 — its only clock was CI's
10-second job poll) and print-only logging (§5.5). Here: a StepTimer with
proper ``block_until_ready`` fencing (XLA is async — wall-clocking a
dispatched-but-unfinished step measures nothing), steps/sec/chip (the
BASELINE.json headline metric), and JSONL metrics next to the human log.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import IO, Any, Dict, List, Optional

import jax

from tpudist.obs import trace as trace_lib


def log0(msg: str) -> None:
    """Rank-0-gated print (parity: reference ``train.py:120-121,128``)."""
    if jax.process_index() == 0:
        print(msg, flush=True)


@dataclass
class StepTimer:
    """Wall-clock over completed device work.

    ``stop(result)`` blocks on ``result`` before reading the clock so the
    measurement covers actual execution, not async dispatch. The first
    ``warmup`` stops (default 1: the trace+compile step) are excluded from
    the throughput aggregate — compile time would otherwise dominate short
    runs and corrupt the steps/sec headline metric.
    """
    warmup: int = 1
    t0: float = 0.0
    elapsed: float = 0.0
    steps: int = 0
    warmup_s: float = 0.0
    _seen: int = 0

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def stop(self, result: Any = None) -> float:
        return self.stop_many(result, 1)

    @property
    def warming(self) -> bool:
        """Still inside the warmup stops (i.e. compile not yet absorbed)."""
        return self._seen < self.warmup

    def stop_many(self, result: Any, n: int) -> float:
        """One fence covering ``n`` dispatched steps (the train loop fences
        at logging boundaries, not per step — a per-step fence serializes
        host and device and drains the dispatch pipeline every step). The
        first group absorbs compile and counts as warmup."""
        if n <= 0:
            return 0.0
        if result is not None:
            # one host transfer both waits for the device and yields
            # the value; on the TPU it agrees with block_until_ready
            # (v5e, PR 21: a 253.6 ms program fenced either way within
            # 0.5 ms; the scalar's copy after a block adds ~0.75 ms)
            with trace_lib.span("fence", cat="dispatch", steps=n):
                jax.device_get(result)
        dt = time.perf_counter() - self.t0
        self._seen += 1
        if self._seen <= self.warmup:
            self.warmup_s += dt
        else:
            self.elapsed += dt
            self.steps += n
        return dt

    def split(self) -> Dict[str, Any]:
        """Compile-vs-run wall split for the metrics stream: the warmup
        fence group absorbs trace+compile (near-zero when the persistent
        compilation cache hits — the pair makes cache effectiveness and
        steady-state dispatch separately visible), ``run_s`` covers the
        counted steady-state steps. FULL precision: downstream MFU math
        divides by ``run_s``, and 3-decimal rounding quantized fast CPU
        test runs to zero — round only for human display."""
        return {"compile_warmup_s": self.warmup_s,
                "run_s": self.elapsed, "steps": self.steps}

    def steps_per_sec(self) -> float:
        return self.steps / self.elapsed if self.elapsed > 0 else 0.0

    def steps_per_sec_per_chip(self) -> float:
        return self.steps_per_sec() / max(jax.device_count(), 1)


@dataclass
class MetricsLogger:
    """JSONL metrics stream, rank-0 only (structured logging the reference
    lacked — its observability was stdout through SLURM log files,
    SURVEY.md §5.5).

    Writes are BUFFERED: ``log()`` on the step path only serialises the
    record into memory; file I/O happens at explicit ``flush()`` points
    (the train loop flushes at epoch ends) and on ``close()``. A
    per-record ``write()+flush()`` put filesystem latency — NFS-mounted
    save dirs are the norm on pods — inside the step loop's timed fence
    windows, where it read as training slowdown in ``StepTimer``.

    CRASH SAFETY: buffering must not mean "lost on death" — the runs
    where metrics matter most are exactly the ones that die between
    flushes. An ``atexit`` hook flushes the tail on any interpreter exit
    (unhandled exception included), and the flight-recorder watchdog
    flushes from its stall dump; a lock makes that cross-thread flush
    safe against the main thread's concurrent ``log()``.

    ``extra`` is stamped into EVERY record (under the record's own
    keys — a record naming ``requeue_attempt`` itself wins): the run
    correlation id + requeue attempt land on every line, so artifacts
    from different attempts of one requeue loop stay correlatable.
    ``emitter`` is the live-telemetry fan-out (obs.live
    ``TelemetryEmitter``): when set, every record ALSO goes onto the
    emitter's bounded non-blocking queue — one ``is not None`` check
    when unset, so ``--live off`` costs nothing.
    """
    path: Optional[str] = None
    _fh: Optional[IO] = None
    history: List[Dict] = field(default_factory=list)
    _buf: List[str] = field(default_factory=list)
    extra: Dict[str, Any] = field(default_factory=dict)
    emitter: Any = None

    def __post_init__(self) -> None:
        import atexit
        import threading
        self._lock = threading.Lock()
        # bound method identity is stable, so close() can unregister it
        atexit.register(self.flush)

    def log(self, **kv) -> None:
        if jax.process_index() != 0:
            return
        # both clocks on every record: wall ``ts`` for humans/dashboards,
        # monotonic ``mono`` (same perf_counter timebase as the span
        # tracer's microsecond stamps) so the offline report CLI aligns
        # metrics with trace spans without trusting NTP
        rec = {"ts": time.time(), "mono": time.perf_counter(),
               **self.extra, **kv}
        with self._lock:
            self.history.append(rec)
            if self.path:
                self._buf.append(json.dumps(rec))
        if self.emitter is not None:
            # live fan-out, OUTSIDE the lock: emit() is a put_nowait
            # that never blocks or raises (obs.live drop-not-block)
            self.emitter.emit(rec)

    def flush(self) -> None:
        """Write buffered records out — called off the step path (epoch
        ends, run end), from the watchdog's stall dump, and from the
        atexit hook, so JSONL I/O never lands inside a timed window and
        a dying run never loses its buffered tail."""
        with self._lock:
            if not (self.path and self._buf):
                return
            if self._fh is None:
                d = os.path.dirname(self.path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._fh = open(self.path, "a")
            self._fh.write("\n".join(self._buf) + "\n")
            self._fh.flush()
            self._buf.clear()

    def close(self) -> None:
        import atexit
        self.flush()
        with self._lock:
            if self._fh:
                self._fh.close()
                self._fh = None
        # a closed logger must not be re-flushed at interpreter exit
        # (the file handle is gone; long-lived processes would also leak
        # one registration per run otherwise)
        try:
            atexit.unregister(self.flush)
        except Exception:
            pass


@dataclass
class StagingStats:
    """Host-side accounting of the epoch staging pipeline
    (train._superstep_epoch): how many bytes were staged, the peak
    resident staging footprint, and how much wall time the host spent
    BLOCKED on a slab that compute was already waiting for.

    ``wait_s`` is the honest exposure metric: the streaming loop fences
    compute at slab boundaries, so by the time it blocks on the next
    slab's readiness the device is idle — any time spent there is
    host→device transfer the pipeline failed to hide behind the previous
    slab's compute. ``overlap_fraction`` folds that into one number for
    the verdict/metrics stream: 1.0 = all steady-state H2D hidden.
    """
    streamed: bool = False
    slabs: int = 0
    staged_bytes: int = 0      # cumulative per-device H2D bytes
    resident_bytes: int = 0
    peak_bytes: int = 0
    stage_host_s: float = 0.0  # host time materialising + dispatching slabs
    wait_s: float = 0.0        # host blocked on an un-arrived slab

    def note_staged(self, nbytes: int, host_s: float) -> None:
        self.slabs += 1
        self.staged_bytes += nbytes
        self.resident_bytes += nbytes
        self.peak_bytes = max(self.peak_bytes, self.resident_bytes)
        self.stage_host_s += host_s

    def note_released(self, nbytes: int) -> None:
        self.resident_bytes = max(0, self.resident_bytes - nbytes)

    def note_wait(self, slab) -> float:
        """Block until ``slab``'s transfer lands; account the exposed
        time. Called with the previous slab's compute already drained."""
        t0 = time.perf_counter()
        with trace_lib.span("slab_wait", cat="staging"):
            jax.block_until_ready(slab)
        dt = time.perf_counter() - t0
        self.wait_s += dt
        return dt

    def overlap_fraction(self, run_s: float) -> Optional[float]:
        """Fraction of steady-state wall time NOT exposed to staging
        waits; None when nothing streamed (fast path: one slab, whose
        transfer overlaps trace+compile by construction)."""
        if not self.streamed or run_s <= 0:
            return None
        return max(0.0, min(1.0, 1.0 - self.wait_s / run_s))

    def split(self) -> Dict[str, Any]:
        """Staging-vs-compute fields for the ``kind=timing`` record."""
        return {"staging_streamed": self.streamed,
                "staging_slabs": self.slabs,
                "staged_bytes": self.staged_bytes,
                "staged_bytes_peak": self.peak_bytes,
                "stage_host_s": round(self.stage_host_s, 3),
                "stage_wait_s": round(self.wait_s, 3)}


def device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"
