"""Measured probe trials of the REAL dispatch path.

One probe = build the exact train-step/superstep program the run would
dispatch (``engine.make_train_step`` / ``engine.make_superstep`` over
``sharding.plan_slabs`` staging — not a model of it), compile it once,
warm it with a full epoch, then time ``repeats`` epochs with host-transfer
fences and report steps/s plus the HBM watermark. The probe either
completes with a number or reports ``feasible=False`` (OOM, a staging
budget that cannot double-buffer, watermark past the device limit) — an
infeasible point is a *result* the search prunes, never a crash.

:class:`EpochRunner` is the compile-once/run-many harness itself; the
streaming path mirrors ``train._superstep_epoch`` — double-buffered
slabs, slab-boundary fences, one compiled superstep for the whole epoch,
padded tail included.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from tpudist import config as config_lib
from tpudist import engine
from tpudist.obs import trace as trace_lib
from tpudist.parallel import sharding as shd

# Probe length/repeats: long enough that per-epoch fixed costs (one
# staging transfer, one fence) amortise like a real epoch, short enough
# that a full search stays a startup blip next to the timed run. The
# estimator over repeats is the MIN epoch time: host-scheduler noise is
# one-sided (a load spike only ever slows an epoch down), so the fastest
# observed epoch is the least-contaminated measurement of the program —
# medians measured up to 20% apart on back-to-back identical CPU probes.
DEFAULT_PROBE_STEPS = 64
DEFAULT_PROBE_REPEATS = 5

# A probe whose HBM watermark lands above this fraction of the device
# limit is pruned even though it survived: the timed run keeps more
# alive (checkpoint snapshots, metrics, the second staged slab at epoch
# scale) and a point with no headroom is one allocator hiccup from OOM.
HBM_HEADROOM_FRACTION = 0.95


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    """One candidate's measured trial (or its reason for being pruned)."""

    steps_per_sec: float
    step_ms: float
    n_steps: int
    repeats: int
    hbm_peak_bytes: Optional[int] = None
    compile_s: float = 0.0
    feasible: bool = True
    error: Optional[str] = None
    key: Optional[tuple] = None   # effective-program key (dedupe)
    counted: bool = True          # False = memo hit, no budget consumed
    spread: float = 0.0           # (max-min)/min over repeats: the trial's
    # own measured noise floor — math-knob commits must clear it


class EpochRunner:
    """Compile-once / run-many epoch harness over the real dispatch path.

    ``k == 1`` runs the per-step path — ``make_train_step`` including its
    per-step ``put_batch`` host transfer, the real thing the superstep
    replaces. ``k > 1`` stages slabs per ``plan_slabs`` (full-epoch fast
    path, or double-buffered streaming under ``budget_bytes``) and
    dispatches supersteps exactly as ``train._superstep_epoch`` does.
    ``dispatch_fn`` exposes the compiled callable (``.cost_analysis()``,
    ``.traces``) for the observability fields the sweeps record.
    """

    def __init__(self, cfg, mesh, k: int, plan, n_steps: int, *,
                 budget_bytes: Optional[int] = None):
        self.cfg, self.mesh, self.k = cfg, mesh, int(k)
        self.n_steps = min(int(n_steps), plan.n_steps)
        if self.n_steps < 1:
            raise ValueError(f"probe needs >= 1 step, got {self.n_steps}")
        self._plan = plan
        if self.k == 1:
            # one host-side gather up front; put_batch stays per-step
            self._host = plan.slab(0, self.n_steps)
            self.dispatch_fn = engine.make_train_step(cfg, mesh)
            self.splan = None
        else:
            batch_shards = max(
                mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1), 1)
            step_bytes = max(1, plan.bytes_per_step * jax.process_count()
                             // batch_shards)
            self.splan = shd.plan_slabs(self.n_steps, self.k, step_bytes,
                                        budget_bytes)
            self.dispatch_fn = engine.make_superstep(cfg, mesh, self.k)

    def init_state(self):
        """A fresh TrainState (each timed epoch donates it away)."""
        return engine.init_state(jax.random.PRNGKey(self.cfg.seed),
                                 self.cfg, self.mesh)

    def run_epoch(self, state) -> Tuple[Any, Any]:
        """Dispatch one epoch; returns ``(state, last_loss)`` with the
        device work still in flight — callers fence on the loss."""
        if self.k == 1:
            loss = None
            for i in range(self.n_steps):
                batch = jax.tree.map(lambda a: a[i], self._host)
                state, loss = self.dispatch_fn(state, batch)
            return state, loss
        splan, k = self.splan, self.k
        S = splan.slab_steps
        total = jnp.zeros((), jnp.float32)
        loss = None

        def stage(s):
            start, stop = s * S, min(self.n_steps, s * S + S)
            pad_to = -(-(stop - start) // k) * k
            return shd.put_epoch(self.mesh,
                                 self._plan.slab(start, stop, pad_to=pad_to))

        nxt = stage(0)
        for s in range(splan.n_slabs):
            cur = nxt
            if s + 1 < splan.n_slabs:
                # double buffer: next slab's H2D overlaps this compute
                nxt = stage(s + 1)
            base = s * S
            staged_len = jax.tree.leaves(cur)[0].shape[0]
            for j in range(staged_len // k):
                gstart = base + j * k
                if gstart >= self.n_steps:
                    break
                hi = min(self.n_steps - gstart, k)
                slab = (cur if staged_len == k else
                        jax.tree.map(lambda a: a[j * k:(j + 1) * k], cur))
                state, total, loss = self.dispatch_fn(state, total, slab,
                                                      0, hi)
            if s + 1 < splan.n_slabs and loss is not None:
                jax.device_get(loss)   # slab-boundary fence (train parity)
        return state, loss


def time_runner(runner: EpochRunner, *, repeats: int = DEFAULT_PROBE_REPEATS,
                state: Any = None) -> Tuple[Any, List[float], float]:
    """Warm (trace+compile+stage) one epoch, then time ``repeats`` epochs.
    Returns ``(state, ms_per_step_per_epoch, compile_s)``; fencing is a
    host transfer of the last loss, as in ``StepTimer.stop_many``."""
    state = runner.init_state() if state is None else state
    t0 = time.perf_counter()
    state, loss = runner.run_epoch(state)
    jax.device_get(loss)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        state, loss = runner.run_epoch(state)
        jax.device_get(loss)
        times.append((time.perf_counter() - t0) * 1000 / runner.n_steps)
    return state, times, compile_s


def candidate_key(cfg, mesh, candidate, plan, n_steps: int) -> tuple:
    """The EFFECTIVE program a candidate dispatches, as a hashable key.
    Distinct candidates can lower to the same program at probe scale
    (every staging budget the probe epoch fits inside is the same
    full-epoch fast path) — the search memoises on this key so the trial
    budget is spent on points that can actually differ. Raises where the
    plan itself is infeasible (plan_slabs's double-buffer error), which
    the caller converts to a pruned point."""
    overlap = (candidate.grad_bucket_mb, candidate.pipeline_interleave)
    if candidate.k == 1:
        return (1, None, candidate.remat, candidate.grad_accum_steps,
                overlap)
    pcfg = candidate.apply(cfg)
    budget = config_lib.resolve_staging_budget_bytes(pcfg)
    n = min(int(n_steps), plan.n_steps)
    batch_shards = max(
        mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1), 1)
    step_bytes = max(1, plan.bytes_per_step * jax.process_count()
                     // batch_shards)
    splan = shd.plan_slabs(n, candidate.k, step_bytes, budget)
    return (candidate.k, (splan.slab_steps, splan.streamed),
            candidate.remat, candidate.grad_accum_steps, overlap)


def probe_candidate(cfg, mesh, candidate, plan, *,
                    n_steps: int = DEFAULT_PROBE_STEPS,
                    repeats: int = DEFAULT_PROBE_REPEATS) -> ProbeResult:
    """Run one candidate's measured trial; never raises — any failure
    (OOM, infeasible slab plan, compile error) comes back as a pruned
    ``feasible=False`` result carrying the error string."""
    from tpudist.obs.hbm import HbmSampler
    n = min(int(n_steps), plan.n_steps)
    try:
        key = candidate_key(cfg, mesh, candidate, plan, n)
        pcfg = candidate.apply(cfg)
        budget = (config_lib.resolve_staging_budget_bytes(pcfg)
                  if candidate.k > 1 else None)
        runner = EpochRunner(pcfg, mesh, candidate.k, plan, n,
                             budget_bytes=budget)
        sampler = HbmSampler(period_s=0)
        # the device runtime's peak_bytes_in_use is a PROCESS-lifetime
        # high-water mark: a prior trial's peak never recedes. Snapshot
        # it before this trial so the headroom prune fires only when
        # THIS candidate raised the watermark past the limit — otherwise
        # one big early trial would poison every later probe
        prior_peak = sampler.peak_in_use
        with trace_lib.span("probe_trial", cat="tune", k=candidate.k,
                            remat=candidate.remat,
                            grad_accum=candidate.grad_accum_steps):
            _, times, compile_s = time_runner(runner, repeats=repeats)
        sampler.sample()
        hbm = sampler.split()
        ms = min(times)   # one-sided noise: fastest epoch is cleanest
        spread = (max(times) - ms) / ms if ms > 0 else 0.0
        peak, limit = hbm["hbm_peak_bytes"], hbm["hbm_limit_bytes"]
        if (peak and limit and hbm["hbm_source"] == "memory_stats"
                and peak > HBM_HEADROOM_FRACTION * limit
                and peak > prior_peak):
            return ProbeResult(
                0.0, ms, n, repeats, hbm_peak_bytes=peak,
                compile_s=compile_s, feasible=False, key=key,
                error=f"hbm watermark {peak} of {limit} B leaves no "
                      f"headroom")
        return ProbeResult(1000.0 / ms, ms, n, repeats,
                           hbm_peak_bytes=peak, compile_s=compile_s,
                           key=key, spread=spread)
    except Exception as e:
        return ProbeResult(0.0, float("inf"), n, repeats, feasible=False,
                           error=f"{type(e).__name__}: {str(e)[:200]}")
