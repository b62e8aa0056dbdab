"""tpudist.obs — the pod flight recorder.

Four pieces that turn a hung or slow pod run into a diagnosis instead
of a timeout (DESIGN.md "Observability"):

  * :mod:`heartbeat` — per-process progress beacon + stall watchdog
    that dumps a flight record *before* the launcher kills the job;
  * :mod:`flightrec` — the dump itself: thread stacks, memory stats,
    last-N metrics, one JSON artifact per worker;
  * :mod:`hbm` — background HBM high-water-mark sampler;
  * :mod:`hoststats` — epoch-end per-host step-time aggregation and
    the three-valued straggler verdict;
  * :mod:`mfu` — MFU/roofline accounting from the compiled program's
    own cost analysis;
  * :mod:`trace` — host-side span tracer (ring buffers, Chrome
    trace-event export, pod-merged Perfetto timeline);
  * :mod:`devtime` — device-time attribution: the jax-free parser for
    ``jax.profiler`` captures (compute vs exposed-communication split)
    plus the ``--profile-window`` capture mode;
  * :mod:`report` — the offline run-report CLI over the merged trace
    plus ``metrics.jsonl`` (``python -m tpudist.obs.report``);
  * :mod:`goodput` — the cross-attempt goodput ledger: productive vs
    badput wall-clock across every requeue attempt of a ``run_id``
    (``python -m tpudist.obs.goodput``);
  * :mod:`memledger` — the per-device HBM ledger: program-derived
    exact bucket partition, headroom grading, and the OOM-forensics
    CLI (``python -m tpudist.obs.memledger``).

:class:`PodObserver` is the facade the train loop wires through: one
object to start, feed progress, ask for record fields, and close.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from tpudist.obs import (devtime, flightrec, hbm, heartbeat, hoststats,
                         mfu, trace)
from tpudist.obs.flightrec import dump_flight_record
from tpudist.obs.hbm import HbmSampler
from tpudist.obs.heartbeat import FlightRecorder
from tpudist.obs.hoststats import HostStepStats

__all__ = ["FlightRecorder", "HbmSampler", "HostStepStats", "PodObserver",
           "devtime", "dump_flight_record", "flightrec", "hbm",
           "heartbeat", "hoststats", "mfu", "trace"]


class PodObserver:
    """The train loop's one observability handle: flight recorder
    (beacon + watchdog), HBM watermark sampler, and per-host straggler
    tracking, started together and closed together.

    Every sub-piece is optional (``stall window 0`` / ``sample period
    0`` disable their threads) and every method degrades to a no-op
    when its piece is off — callers never branch.
    """

    def __init__(self, *, out_dir: str, stall_timeout_s: float = 300.0,
                 hbm_sample_s: float = 2.0, metrics: Any = None,
                 process_index: int = 0, process_count: int = 1,
                 stall_hook: Any = None, live: Any = None,
                 live_fields: Any = None, requeue_attempt: int = 0):
        self.hbm = (HbmSampler(period_s=hbm_sample_s)
                    if hbm_sample_s > 0 else None)
        self.hosts = HostStepStats(process_index=process_index,
                                   process_count=process_count)
        self.live = live
        # the last assembled HBM ledger (obs.memledger): the train and
        # serve loops store it here so a pre-kill flight record carries
        # the final bucket partition — the OOM forensics CLI's
        # reconstruct-from-artifacts input
        self.last_memledger: Optional[Dict[str, Any]] = None

        def _extra_state() -> Dict[str, Any]:
            # the flight-record extras: HBM watermarks, plus — on the
            # coordinator of a live run — the aggregator's last
            # rolling-window snapshot (lock-free wholesale-replaced
            # dict, obs.live), so a pre-kill dump says what the POD
            # looked like, not just this process
            out = dict(self.hbm.split()) if self.hbm is not None else {}
            if self.last_memledger is not None:
                out["memledger"] = self.last_memledger
            if live is not None:
                snap = live.snapshot_fields()
                if snap is not None:
                    out["live_status"] = snap
            return out

        def _beacon_extra() -> Dict[str, Any]:
            # live slice of the heartbeat beacon: the SAME observables
            # the exit verdict grades (staging overlap inputs, HBM
            # peak), cheap counter reads only — no fences, no jax
            out: Dict[str, Any] = {}
            if self.hbm is not None:
                out["hbm_peak_bytes"] = self.hbm.peak_in_use or None
            if live_fields is not None:
                try:
                    out.update(live_fields())
                except Exception:
                    pass
            return out

        self.recorder = FlightRecorder(
            out_dir, stall_timeout_s=stall_timeout_s,
            process_index=process_index, metrics=metrics,
            extra_state=_extra_state,
            tracer=trace.get(), stall_hook=stall_hook,
            emitter=(live.emitter if live is not None else None),
            beacon_extra=_beacon_extra,
            requeue_attempt=requeue_attempt)
        self._closed = False

    @classmethod
    def from_config(cls, cfg, *, metrics=None, process_index: int = 0,
                    process_count: int = 1,
                    stall_hook: Any = None, live: Any = None,
                    live_fields: Any = None) -> "PodObserver":
        from tpudist.config import resolve_obs, resolve_requeue_attempt
        stall_s, out_dir, hbm_s = resolve_obs(cfg)
        return cls(out_dir=out_dir, stall_timeout_s=stall_s,
                   hbm_sample_s=hbm_s, metrics=metrics,
                   process_index=process_index,
                   process_count=process_count, stall_hook=stall_hook,
                   live=live, live_fields=live_fields,
                   requeue_attempt=resolve_requeue_attempt(cfg))

    def note_progress(self, **kv: Any) -> None:
        self.recorder.note_progress(**kv)

    def beacon_now(self) -> None:
        """One synchronous beacon write (the scripted-kill stamp —
        FlightRecorder.beacon_now)."""
        self.recorder.beacon_now()

    def epoch_end(self, epoch: int, timer, metrics) -> str:
        """Per-host step-stat aggregation (collective on multi-host —
        every process must call this at every epoch end)."""
        return self.hosts.epoch_end(epoch, timer, metrics)

    def hbm_fields(self) -> Dict[str, Any]:
        if self.hbm is None:
            # same schema as HbmSampler.split: every hbm_* key present
            # in every timing record, None = not derived (parsers must
            # not key-error on degraded runs)
            return {"hbm_peak_bytes": None, "hbm_bytes_in_use": None,
                    "hbm_peak_bytes_per_device": None,
                    "hbm_bytes_reserved": None,
                    "hbm_limit_bytes": None, "hbm_peak_fraction": None,
                    "hbm_source": "off"}
        self.hbm.sample()   # final watermark before the record is cut
        return self.hbm.split()

    def timing_fields(self, timer, dispatch_fn: Any) -> Dict[str, Any]:
        """The observability slice of the run-end ``kind=timing``
        record: MFU/roofline from the compiled dispatch, HBM
        watermarks, and the last epoch's straggler verdict."""
        step_s = (timer.elapsed / timer.steps) if timer.steps else 0.0
        fields = mfu.mfu_fields(mfu.dispatch_cost(dispatch_fn), step_s)
        fields.update(self.hbm_fields())
        fields["straggler_status"] = self.hosts.status
        return fields

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.recorder.close()
        if self.hbm is not None:
            self.hbm.close()
