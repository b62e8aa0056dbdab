"""Configuration system for the workload.

The reference scattered configuration over four ad-hoc surfaces with broken
precedence (three conflicting batch sizes — reference ``train.py:44,74,79``,
SURVEY.md §2.7). Here there is exactly ONE config object with explicit
precedence: defaults < CLI flags. The CLI remains tolerant of unknown flags
for parity with the reference's ``parse_known_args`` contract
(reference ``train.py:49``).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass
from typing import Any, Optional, Sequence

from tpudist import rules as rules_lib


# context-parallel attention implementations (single source of truth;
# tpudist.models.transformer imports this for its validation/errors)
CP_IMPLS = ("ring", "ulysses")


@dataclass(frozen=True)
class DataConfig:
    """Synthetic dataset shape (parity: reference ``train.py:19-24,63``)."""

    n_samples: int = 2000
    n_features: int = 20
    seed: int = 42


@dataclass(frozen=True)
class ModelConfig:
    """Model selection. ``mlp`` is the parity model (reference
    ``train.py:26-36``); ``transformer`` is the north-star synthetic
    Llama-block model (BASELINE.json config #5)."""

    name: str = "mlp"
    n_features: int = 20
    hidden: int = 64
    # transformer-only fields
    vocab_size: int = 32000
    n_layers: int = 4
    d_model: int = 2048
    n_heads: int = 16
    n_kv_heads: int = 16
    d_ff: int = 5504
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # moe-only fields (model name "moe": transformer blocks with a
    # mixture-of-experts FFN, experts sharded over the mesh's expert axis)
    n_experts: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_group_size: int = 4096    # routing group (bounds dispatch memory)
    # cohere2moe-only fields (model name "cohere2moe": parallel block,
    # window and NoPE-full attention by layer, sigmoid-routed experts of
    # which this chip holds a share, averaged shared experts). It reads
    # n_experts (router width), expert_top_k and d_ff (one expert's
    # width) too
    head_dim: int = 0             # 0: d_model // n_heads (see head_size)
    sliding_window: int = 0       # keys a window layer's query sees,
    # itself included; 0: the model has no window layers
    full_attn_every: int = 4      # layer l is full iff (l+1) % this == 0
    n_experts_held: int = 0       # how many experts live here; 0: all
    expert_first: int = 0         # the first of them (one chip's share of
    # an expert-parallel layer is experts first .. first + held - 1)
    n_shared_experts: int = 0     # averaged beside the routed sum
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    # sdarmoe-only fields (model name "sdarmoe": generation by diffusion
    # over blocks). It reads n_experts, expert_top_k, d_ff (one expert's
    # width), head_dim and norm_eps too
    block_length: int = 0         # positions a dispatch denoises and
    # commits together; 0: the model generates a token a step
    denoise_steps: int = 0        # denoising forwards a block (the commit
    # forward comes on top); 0: block_length, one position a step
    mask_token_id: int = 0        # what a position still masked holds
    # longcatflash-only fields (model name "longcatflash": latent attention
    # (MLA) over a latent paged cache, two attention sublayers and two
    # dense FFNs a layer around one expert mix, identity experts). It
    # reads n_experts (the router's real experts), n_experts_held,
    # expert_first, expert_top_k, d_ff (one expert's width) and norm_eps
    # too. The model module declares them its own (``CONFIG_FIELDS``):
    # ``models.model_for`` refuses them set for a model that claims none
    q_lora_rank: int = 0          # width of the query's latent
    kv_lora_rank: int = 0         # width of the cached latent; > 0 makes
    # the serve cache latent: one row a token an attention sublayer
    qk_nope_head_dim: int = 0     # a head's query/key part without rope
    qk_rope_head_dim: int = 0     # ... and with: one key shared by all heads
    v_head_dim: int = 0           # a head's value
    n_zero_experts: int = 0       # identity experts the router also scores
    routed_scaling: float = 1.0   # times every chosen weight
    d_ff_dense: int = 0           # width of the dense FFNs beside the mix

    @property
    def head_size(self) -> int:
        """Width of one attention head: the ONE place that says it."""
        return self.head_dim or self.d_model // self.n_heads

    @property
    def latent_row(self) -> int:
        """Values of one cached row of a latent cache as it is STORED: the
        latent and the shared rope key (``kv_lora_rank +
        qk_rope_head_dim``: 576) up to whole lanes of 128 (640: the rest
        are dead lanes, written 0 and scored against 0). 0 for a model
        without latent attention."""
        if not self.kv_lora_rank:
            return 0
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh axis sizes. ``-1`` on the data axis means "all remaining
    devices". A size of 1 disables that axis (it still exists in the mesh so
    shardings are uniform across configurations)."""

    data: int = -1
    pipe: int = 1
    fsdp: int = 1
    expert: int = 1
    tensor: int = 1
    context: int = 1


@dataclass(frozen=True)
class TrainConfig:
    """Top-level workload config (parity: reference ``train.py:42-49`` flags
    plus the ds_config dict at ``train.py:78-83``, unified)."""

    batch_size: int = 64          # GLOBAL batch size (one source of truth)
    epochs: int = 5
    lr: float = 1e-3
    seed: int = 42
    save_dir: str = "ckpt"
    resume: Any = False           # False = off; True/"latest" = resume and
    # RAISE if the newest checkpoint cannot drive this run; "auto" = the
    # launcher-requeue mode — resume when a committed checkpoint exists,
    # fall back to a fresh start (flagged resume_status=fail) when the
    # restore errors, never crash-loop (resolve_resume)
    ckpt_every_steps: int = 0     # also save mid-epoch every N steps (0=off)
    ckpt_sync: bool = False       # disable async checkpointing (debugging)
    ckpt_mode: Optional[str] = None  # orbax | sharded (elastic/ckpt.py:
    # per-worker shard files + atomically committed manifest — the
    # reshardable layout elastic resume consumes). None =
    # $TPUDIST_CKPT_MODE, else orbax (resolve_ckpt_mode)
    requeue_attempt: int = 0      # which auto-requeue rerun this is (the
    # launcher passes it; 0 = first attempt / not requeued). Rides into
    # the kind=resume record / resume_status line
    # ($TPUDIST_REQUEUE_ATTEMPT when 0)
    grad_accum_steps: int = 1
    dtype: str = "float32"        # compute dtype: float32 | bfloat16
    adam_nu_dtype: str = "float32"  # Adam second-moment storage dtype
    # (bfloat16 = opt-in HBM saving for big optimizer states, engine.py)
    remat: bool = False           # checkpoint transformer layers
    xent_chunks: int = 0          # stream LM head+loss over N seq chunks
    fused_xent: bool = False      # pallas fused LM head+loss (no HBM logits)
    lm_head: str = "auto"         # auto | plain | chunked | fused — auto
    # defers to fused_xent/xent_chunks when set, else picks by the memory
    # policy (models.transformer.pick_lm_head)
    pp_microbatches: int = 0      # pipeline microbatches (0 = pipe size)
    pipeline_interleave: int = 0  # virtual stages per pipeline device
    # (parallel.pipeline interleaved schedule): v>1 cuts the bubble from
    # (S-1)/(M+S-1) to (S-1)/(v*M+S-1) by giving each device v
    # round-robin layer chunks. 0 = $TPUDIST_PIPELINE_INTERLEAVE, else 1
    # (the GPipe parity oracle)
    cp_impl: str = "ring"         # context parallelism: ring | ulysses
    grad_overlap: Optional[str] = None  # off | bucketed — DP gradient
    # all-reduce schedule (parallel.overlap): off pins the trailing-
    # barrier baseline (reduce after the whole backward), bucketed
    # splits the reduce into size-bounded buckets dispatched as the
    # backward produces each bucket's grads, hidden behind the
    # remaining backward compute (the multi-slice DCN recipe). None =
    # $TPUDIST_GRAD_OVERLAP, else off. Bitwise-identical loss either
    # way; only the schedule (and the exposed-comm fraction) moves
    grad_bucket_mb: Optional[float] = None  # bucket size bound in MB for
    # --grad-overlap bucketed. None = $TPUDIST_GRAD_BUCKET_MB, else 4
    cross_slice: Optional[str] = None  # flat | hierarchical — how the DP
    # gradient reduce crosses slice boundaries (parallel.overlap): flat
    # moves the FULL gradient bytes over DCN (in-slice reduce, then
    # cross-slice reduce on the whole vector), hierarchical
    # reduce-scatters in-slice over ICI, all-reduces the 1/slice_size
    # shard over DCN, all-gathers in-slice — DCN bytes drop by the
    # slice size. Bitwise-identical loss either way (both modes pin the
    # same slice-structured association); single-slice meshes downgrade
    # hierarchical to flat with a logged notice. None =
    # $TPUDIST_CROSS_SLICE, else flat
    fail_at: Optional[int] = None  # fault injection: exit(1) after this epoch
    chaos: Optional[str] = None   # scripted fault-injection plan
    # (tpudist.chaos): ";"-separated <fault>@<epoch>:<step>[:<rank>]
    # [,k=v...] events — kill | hang | slow | corrupt_shard |
    # torn_manifest | fs_error | telemetry_garbage. None =
    # $TPUDIST_CHAOS, else off (resolve_chaos). Deterministic by
    # construction: the same spec replays the same faults
    log_every: int = 100
    profile_dir: Optional[str] = None  # write jax.profiler traces here
    profile_window: int = 0       # capture N mid-run supersteps with
    # jax.profiler into <trace-dir>/profile/worker<i> and ingest the
    # device timeline at run end (obs.devtime: kind=devtime record,
    # device tracks in pod_trace.json, comm_status). 0 = off
    # ($TPUDIST_PROFILE_WINDOW). Unlike --profile-dir this is cheap,
    # keeps superstep dispatch, and composes with --autotune probe
    steps_per_dispatch: int = 0   # superstep length k: one compiled
    # lax.scan dispatch covers k train steps (engine.make_superstep).
    # 0 = auto (resolve_steps_per_dispatch); 1 = per-step dispatch.
    staging_budget_mb: Optional[float] = None  # per-device MB of batch
    # staging memory (sharding.plan_slabs). None = $TPUDIST_STAGING_BUDGET_MB,
    # else auto from device memory stats minus the train-state estimate
    # (resolve_staging_budget_bytes); epochs over budget stream in
    # double-buffered slabs instead of staging whole
    stall_timeout_s: Optional[float] = None  # flight-recorder watchdog: no
    # step progress for this long -> dump stacks/memory/last-metrics to
    # flightrec.worker<i> (obs.heartbeat). None = $TPUDIST_STALL_TIMEOUT_S,
    # else 300; 0 disables the watchdog (the heartbeat beacon still beats)
    heartbeat_dir: Optional[str] = None  # where heartbeat.worker<i> /
    # flightrec.worker<i> land. None = $TPUDIST_HEARTBEAT_DIR, else save_dir
    hbm_sample_s: Optional[float] = None  # HBM watermark sampler period
    # (obs.hbm). None = $TPUDIST_HBM_SAMPLE_S, else 2.0; 0 disables
    autotune: Optional[str] = None  # off | probe | cache-only
    # (tpudist.tune): measure the dispatch/staging/remat operating point
    # with short on-device trials before the timed run, or reuse a
    # cached measurement. None = $TPUDIST_AUTOTUNE, else off.
    autotune_cache_dir: Optional[str] = None  # tuning-cache directory.
    # None = $TPUDIST_AUTOTUNE_CACHE_DIR, else <save_dir>/tune
    autotune_trials: int = 0      # probe-trial budget; 0 = auto
    # ($TPUDIST_AUTOTUNE_TRIALS, else 12)
    trace: Optional[str] = None   # on | off — host-side span tracing
    # (obs.trace): ALWAYS ON by default; None = $TPUDIST_TRACE, else on.
    # Run end exports trace.worker<i>.json per process and a merged
    # pod_trace.json on the coordinator (one Perfetto track per host)
    trace_dir: Optional[str] = None  # where trace artifacts land.
    # None = $TPUDIST_TRACE_DIR, else save_dir (next to metrics.jsonl)
    live: Optional[str] = None    # on | off — live telemetry bus
    # (obs.live): per-worker non-blocking emitters stream records +
    # heartbeats to a coordinator aggregator that keeps rolling
    # windows, runs the on-line alert engine over the SAME thresholds
    # the exit verdict applies (tpudist.rules), rewrites
    # live_status.json, and serves Prometheus /metrics.
    # None = $TPUDIST_LIVE, else off (resolve_live)
    live_port: int = 0            # Prometheus exporter port on the
    # coordinator (/metrics, /status.json, /healthz). 0 =
    # $TPUDIST_LIVE_PORT, else an ephemeral port
    live_endpoint: Optional[str] = None  # ingest endpoint workers ship
    # records to ([tcp://|udp://]host:port). None =
    # $TPUDIST_LIVE_ENDPOINT, else the coordinator binds loopback on an
    # ephemeral port (single-host runs); the launcher passes the
    # coordinator's reachable address on pods
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)


# auto superstep cap: past ~32 steps per dispatch the per-dispatch
# overhead is already amortised to noise and longer scans only delay
# log/fence boundaries (a CPU sweep of a toy preset said so; on the
# chip the curve is not measured: ROADMAP C3)
SUPERSTEP_CAP = 32


def resolve_steps_per_dispatch(cfg: TrainConfig) -> int:
    """Resolve/validate ``--steps-per-dispatch`` to the concrete superstep
    length ``k`` for this run.

    The train loop only fences and logs at superstep edges, so ``k`` must
    divide ``--log-every`` and ``--ckpt-every-steps`` (when enabled) —
    boundaries then land exactly on superstep edges and the logged
    loss/step stream is indistinguishable from per-step dispatch. An
    explicit ``k`` violating that is a config error, as is ``k > 1``
    combined with ``--fail-at`` (fault-injection timing is defined in
    per-step terms; a k-step scan would glide past the injection point).

    Auto (``0``) picks 1 under ``--log-every 1``, profiling, or fault
    injection (each wants true per-step dispatch), else the largest
    divisor of the log/ckpt intervals ≤ :data:`SUPERSTEP_CAP`. The
    epoch's trailing partial superstep is NOT a config concern: it is
    zero-padded to ``k`` with the pad steps masked out of the loss and
    state updates, so ONE compiled program serves the whole run
    (engine.make_superstep).
    """
    k = cfg.steps_per_dispatch
    if k < 0:
        raise ValueError(
            f"--steps-per-dispatch must be >= 1 (or 0 = auto), got {k}")
    if k == 0:
        if cfg.profile_dir or cfg.fail_at is not None or cfg.log_every == 1:
            return 1
        cap = SUPERSTEP_CAP if cfg.log_every <= 0 else min(cfg.log_every,
                                                           SUPERSTEP_CAP)
        best = 1
        for d in range(1, cap + 1):
            if cfg.log_every > 0 and cfg.log_every % d:
                continue
            if cfg.ckpt_every_steps and cfg.ckpt_every_steps % d:
                continue
            best = d
        return best
    if k > 1:
        if cfg.fail_at is not None:
            raise ValueError(
                f"--steps-per-dispatch {k} with --fail-at: fault injection "
                f"must observe per-step/epoch boundaries; use "
                f"--steps-per-dispatch 1")
        if cfg.log_every > 0 and cfg.log_every % k:
            raise ValueError(
                f"--steps-per-dispatch {k} must divide --log-every "
                f"{cfg.log_every} so logging boundaries land on superstep "
                f"edges")
        if cfg.ckpt_every_steps and cfg.ckpt_every_steps % k:
            raise ValueError(
                f"--steps-per-dispatch {k} must divide --ckpt-every-steps "
                f"{cfg.ckpt_every_steps} so checkpoint boundaries land on "
                f"superstep edges")
    return k


# Auto staging budget: leave the train state (params + opt moments) plus
# this multiple of it for grads / activations / XLA workspace, then stage
# batches into half of what remains (the other half is slack for the
# allocator — device memory stats are an estimate, not a reservation).
# The floor keeps the budget positive when the conservative 4x estimate
# exceeds the device estimate: a zero budget would make plan_slabs
# reject EVERY epoch, failing runs that used to stage fine.
STAGING_STATE_HEADROOM = 4.0
STAGING_FREE_FRACTION = 0.5
STAGING_FLOOR_FRACTION = 0.05


def resolve_staging_budget_bytes(cfg: TrainConfig, *, state_bytes: int = 0,
                                 hbm_bytes: Optional[float] = None,
                                 program_temp_bytes: Optional[int] = None
                                 ) -> Optional[int]:
    """Resolve ``--staging-budget-mb`` to a per-device byte budget for
    epoch staging (``sharding.plan_slabs``), or ``None`` for "unbounded"
    (always the full-epoch fast path).

    Precedence: explicit flag > ``TPUDIST_STAGING_BUDGET_MB`` > auto.
    Auto derives from the device's reported memory minus the train
    state and its working margin — ledger-informed when a prior run's
    memory ledger measured the compiled programs' real scratch
    (``program_temp_bytes``, obs.memledger): the margin is then
    ``state + measured temp`` instead of the conservative
    ``STAGING_STATE_HEADROOM x state`` guess (the 4x heuristic stays
    the fallback; the train loop logs which path won). The budget only
    moves slab CUT points, which the superstep's lo/hi masking keeps
    loss-invariant — so a ledger-informed budget is bitwise
    loss-neutral by construction (pinned in tests). On backends that
    report no limit (CPU tests) the 16 GB default makes small epochs
    take the fast path, which is exactly the seed behavior.
    """
    mb = cfg.staging_budget_mb
    if mb is None:
        env = os.environ.get("TPUDIST_STAGING_BUDGET_MB")
        if env:
            mb = float(env)
    if mb is not None:
        if mb <= 0:
            raise ValueError(
                f"--staging-budget-mb must be > 0, got {mb}")
        return int(mb * 2**20)
    if hbm_bytes is None:
        return None
    if program_temp_bytes is not None and program_temp_bytes >= 0:
        margin = state_bytes + program_temp_bytes
    else:
        margin = STAGING_STATE_HEADROOM * state_bytes
    free = max(hbm_bytes - margin, hbm_bytes * STAGING_FLOOR_FRACTION)
    return int(free * STAGING_FREE_FRACTION)


# Autotune (tpudist.tune): the measured-probe search that replaces the
# two resolve_* heuristics above with a measurement when enabled. The
# heuristics stay as the search's START point and its never-regress
# floor.
AUTOTUNE_MODES = ("off", "probe", "cache-only")
AUTOTUNE_DEFAULT_TRIALS = 12


def resolve_autotune(cfg: TrainConfig) -> str:
    """Resolve ``--autotune`` / ``TPUDIST_AUTOTUNE`` to a concrete mode.

    ``probe`` measures on a cache miss; ``cache-only`` reuses a prior
    measurement but never probes (pod launches where N workers probing
    at startup is unwanted). Fault injection and FULL-RUN profiling
    (``--profile-dir``) force ``off``: both are defined in
    per-step-dispatch terms, so every knob the tuner searches is
    already pinned. The windowed capture (``--profile-window``) does
    NOT force off — it profiles whatever operating point the run
    actually uses, tuned or not, and runs long after the probes are
    done (pinned in tests/test_devtime.py).
    """
    mode = cfg.autotune
    if mode is None:
        mode = os.environ.get("TPUDIST_AUTOTUNE") or "off"
    if mode not in AUTOTUNE_MODES:
        raise ValueError(
            f"--autotune must be one of {AUTOTUNE_MODES}, got {mode!r}")
    if mode != "off" and (cfg.fail_at is not None or cfg.profile_dir):
        return "off"
    return mode


def resolve_profile_window(cfg: TrainConfig) -> int:
    """Resolve ``--profile-window`` / ``TPUDIST_PROFILE_WINDOW`` to the
    number of mid-run supersteps to capture (0 = off).

    Precedence: explicit flag > env > 0. Full-run ``--profile-dir``
    wins over the window (profiler sessions cannot nest — the whole
    run is already inside one), so the window resolves to 0 there.
    """
    n = cfg.profile_window
    if n < 0:
        raise ValueError(
            f"--profile-window must be >= 0, got {n}")
    if n == 0:
        env = _env_float("TPUDIST_PROFILE_WINDOW")
        n = int(env) if env and env > 0 else 0
    if cfg.profile_dir:
        return 0
    return n


def resolve_autotune_cache_dir(cfg: TrainConfig) -> str:
    """Precedence: flag > ``TPUDIST_AUTOTUNE_CACHE_DIR`` > a ``tune/``
    subdir of ``save_dir`` (next to metrics.jsonl — one directory to
    persist across runs, same shape as the heartbeat default)."""
    return (cfg.autotune_cache_dir
            or os.environ.get("TPUDIST_AUTOTUNE_CACHE_DIR")
            or os.path.join(cfg.save_dir, "tune"))


def resolve_autotune_trials(cfg: TrainConfig) -> int:
    """Probe-trial budget: flag > ``TPUDIST_AUTOTUNE_TRIALS`` > 12."""
    if cfg.autotune_trials < 0:
        raise ValueError(
            f"--autotune-trials must be >= 0, got {cfg.autotune_trials}")
    if cfg.autotune_trials:
        return cfg.autotune_trials
    env = _env_float("TPUDIST_AUTOTUNE_TRIALS")
    return int(env) if env and env > 0 else AUTOTUNE_DEFAULT_TRIALS


# Gradient-overlap plane (tpudist.parallel.overlap): the DP all-reduce
# schedule knob and its bucket bound. The default bucket mirrors
# overlap.DEFAULT_BUCKET_MB (kept as a literal here so config stays
# importable before jax — the two are pinned equal in tests).
GRAD_OVERLAP_MODES = ("off", "bucketed")
GRAD_BUCKET_MB_DEFAULT = 4.0


def resolve_grad_overlap(cfg: TrainConfig) -> tuple[str, int]:
    """Resolve ``--grad-overlap`` / ``--grad-bucket-mb`` to the concrete
    ``(mode, bucket_bytes)`` pair the engine's DP path dispatches on.

    Precedence per knob: explicit flag > env (``TPUDIST_GRAD_OVERLAP``,
    ``TPUDIST_GRAD_BUCKET_MB``) > default (off, 4 MB). The mode applies
    to the explicit-collective DP shard_map path only — the engine
    raises on meshes that route gradients through the jit+shardings
    partitioner (there is no program-level reduce there to schedule)."""
    mode = cfg.grad_overlap
    if mode is None:
        mode = os.environ.get("TPUDIST_GRAD_OVERLAP") or "off"
    if mode not in GRAD_OVERLAP_MODES:
        raise ValueError(
            f"--grad-overlap must be one of {GRAD_OVERLAP_MODES}, "
            f"got {mode!r}")
    mb = cfg.grad_bucket_mb
    if mb is None:
        mb = _env_float("TPUDIST_GRAD_BUCKET_MB")
    if mb is None:
        mb = GRAD_BUCKET_MB_DEFAULT
    if mb <= 0:
        raise ValueError(f"--grad-bucket-mb must be > 0, got {mb}")
    return mode, int(mb * 2**20)


# --cross-slice vocabulary, mirrored from overlap.CROSS_SLICE_MODES
# (kept as a literal so config stays importable before jax — pinned
# equal in tests, like GRAD_OVERLAP_MODES above).
CROSS_SLICE_MODES = ("flat", "hierarchical")


def resolve_cross_slice(cfg: TrainConfig) -> str:
    """Resolve ``--cross-slice`` to the concrete cross-slice reduce
    schedule. Precedence: explicit flag > ``TPUDIST_CROSS_SLICE`` >
    flat. Like --grad-overlap, the mode applies to the explicit-
    collective pure-DP path; the engine refuses hierarchical on meshes
    that route gradients through the jit+shardings partitioner and
    downgrades it (with a logged notice) on single-slice meshes, where
    there is no DCN phase to split."""
    mode = cfg.cross_slice
    if mode is None:
        mode = os.environ.get("TPUDIST_CROSS_SLICE") or "flat"
    if mode not in CROSS_SLICE_MODES:
        raise ValueError(
            f"--cross-slice must be one of {CROSS_SLICE_MODES}, "
            f"got {mode!r}")
    return mode


def resolve_pipeline_interleave(cfg: TrainConfig) -> int:
    """Resolve ``--pipeline-interleave`` to the virtual-stage count v
    (1 = GPipe). Precedence: explicit flag > env > 1. Divisibility
    against the layer/stage/microbatch shape is validated where those
    are known (parallel.pipeline.make_pp_loss_fn)."""
    v = cfg.pipeline_interleave
    if v < 0:
        raise ValueError(
            f"--pipeline-interleave must be >= 1 (or 0 = default), "
            f"got {v}")
    if v == 0:
        env = _env_float("TPUDIST_PIPELINE_INTERLEAVE")
        v = int(env) if env and env > 0 else 1
    return v


# Elastic checkpoint/resume (tpudist.elastic): the checkpoint layout and
# the resume semantics are separate knobs — the layout decides what a
# kill can lose, the resume mode decides what a restart does about it.
CKPT_MODES = ("orbax", "sharded")
RESUME_MODES = ("latest", "auto")


def resolve_ckpt_mode(cfg: TrainConfig) -> str:
    """Resolve ``--ckpt-mode`` / ``TPUDIST_CKPT_MODE`` to the concrete
    checkpoint layout: ``orbax`` (step-keyed CheckpointManager — the
    default, and the only mode that writes ``gs://`` URIs natively) or
    ``sharded`` (tpudist.elastic.ckpt: per-worker shard files + an
    atomically committed manifest on a pod-shared filesystem — the
    layout elastic N→M resume reshards from). ``--ckpt-sync`` composes
    with either: it selects synchronous writes within the mode."""
    mode = cfg.ckpt_mode
    if mode is None:
        mode = os.environ.get("TPUDIST_CKPT_MODE") or "orbax"
    if mode not in CKPT_MODES:
        raise ValueError(
            f"--ckpt-mode must be one of {CKPT_MODES}, got {mode!r}")
    if mode == "sharded" and "://" in cfg.save_dir:
        raise ValueError(
            f"--ckpt-mode sharded writes plain files on a pod-shared "
            f"filesystem and cannot target {cfg.save_dir!r}; keep "
            f"--ckpt-mode orbax for remote URIs (or mount the bucket)")
    return mode


def resolve_resume(cfg: TrainConfig) -> Optional[str]:
    """Resolve ``--resume`` to a concrete mode or None (off). ``True``
    (the pre-elastic boolean spelling, kept for compat) means
    ``latest``. ``latest`` raises when the newest checkpoint cannot
    drive this run; ``auto`` — what the launcher's requeue loop passes —
    degrades a failed restore to a flagged fresh start, because a
    requeued job must make progress, not crash-loop on a torn dir."""
    r = cfg.resume
    if not r:
        return None
    if r is True:
        return "latest"
    if r not in RESUME_MODES:
        raise ValueError(
            f"--resume must be one of {RESUME_MODES}, got {r!r}")
    return r


def resolve_chaos(cfg: TrainConfig) -> Optional[str]:
    """Resolve ``--chaos`` / ``TPUDIST_CHAOS`` to the raw fault-plan
    spec, or None (the default: no chaos plane constructed, zero hooks
    installed). The spec itself is parsed — and validated loudly — by
    ``tpudist.chaos.ChaosPlan.parse`` at run start, not here: config
    must stay importable without the chaos package resolved."""
    return cfg.chaos or os.environ.get("TPUDIST_CHAOS") or None


def resolve_requeue_attempt(cfg: TrainConfig) -> int:
    """Which auto-requeue rerun this is: explicit flag, else
    ``TPUDIST_REQUEUE_ATTEMPT``, else 0."""
    if cfg.requeue_attempt:
        return int(cfg.requeue_attempt)
    env = _env_float("TPUDIST_REQUEUE_ATTEMPT")
    return int(env) if env and env > 0 else 0


# Span tracing (tpudist.obs.trace): always-on observability, like the
# flight recorder — the escape hatch exists for runs measuring the last
# microsecond of host overhead, not as the default posture.
TRACE_MODES = ("on", "off")


def resolve_trace(cfg: TrainConfig) -> tuple[bool, str]:
    """Resolve the span-tracer knobs to ``(enabled, trace_dir)``.

    Precedence per knob: explicit flag > env var > default (on,
    ``save_dir``). ``TPUDIST_TRACE`` accepts the usual falsy spellings
    (off/0/false/no) so launchers can disable tracing pod-wide without
    touching per-worker argv."""
    mode = cfg.trace
    if mode is None:
        # single source of truth for the accepted falsy spellings: the
        # ambient tracer (obs.trace.get, used by bench/selfcheck paths
        # that never call this resolver) parses the same env the same
        # way. Lazy import: config must stay importable before jax.
        from tpudist.obs.trace import _env_enabled
        mode = "on" if _env_enabled() else "off"
    if mode not in TRACE_MODES:
        raise ValueError(
            f"--trace must be one of {TRACE_MODES}, got {mode!r}")
    out_dir = (cfg.trace_dir or os.environ.get("TPUDIST_TRACE_DIR")
               or cfg.save_dir)
    return mode == "on", out_dir


# Flight-recorder defaults: the stall window must comfortably exceed any
# legitimate quiet period (a cold compile of the flagship superstep is
# ~1-2 min on TPU) while still firing well inside the launcher's outer
# TIMEOUT_S (default 1800) — the dump has to land BEFORE the kill. The
# value itself lives in tpudist.rules: the live alert engine fires the
# stall alert on the SAME window the watchdog dumps on.
OBS_STALL_TIMEOUT_S = rules_lib.STALL_TIMEOUT_S
OBS_HBM_SAMPLE_S = 2.0


def _env_float(name: str) -> Optional[float]:
    """Optional float env var; a malformed value reads as unset (an
    advisory observability knob must never kill a run at startup —
    same swallow-and-default semantics as verdict._env_float). An
    explicit FLAG, by contrast, still raises below: typos on the
    command line should fail fast."""
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def resolve_obs(cfg: TrainConfig) -> tuple[float, str, float]:
    """Resolve the flight-recorder knobs to concrete values:
    ``(stall_timeout_s, out_dir, hbm_sample_s)``.

    Precedence per knob: explicit flag > env var > default. The beacon /
    flight-record directory defaults to ``save_dir`` so the artifacts
    land next to ``metrics.jsonl`` — one directory to collect when a run
    dies.
    """
    stall = cfg.stall_timeout_s
    if stall is None:
        stall = _env_float("TPUDIST_STALL_TIMEOUT_S")
    if stall is None:
        stall = OBS_STALL_TIMEOUT_S
    if stall < 0:
        raise ValueError(f"--stall-timeout-s must be >= 0, got {stall}")
    out_dir = (cfg.heartbeat_dir or os.environ.get("TPUDIST_HEARTBEAT_DIR")
               or cfg.save_dir)
    hbm_s = cfg.hbm_sample_s
    if hbm_s is None:
        hbm_s = _env_float("TPUDIST_HBM_SAMPLE_S")
    if hbm_s is None:
        hbm_s = OBS_HBM_SAMPLE_S
    if hbm_s < 0:
        raise ValueError(f"--hbm-sample-s must be >= 0, got {hbm_s}")
    return stall, out_dir, hbm_s


# Live telemetry (tpudist.obs.live): OFF by default — unlike the span
# tracer it opens sockets and threads, which a bare acceptance run
# should not do unless an operator (or the launcher) asked for the view.
LIVE_MODES = ("on", "off")


def resolve_live(cfg: TrainConfig) -> tuple[bool, int, Optional[str]]:
    """Resolve the live-telemetry knobs to ``(enabled, exporter_port,
    ingest_endpoint)``.

    Precedence per knob: explicit flag > env var > default (off, 0 =
    ephemeral exporter port, no endpoint). ``TPUDIST_LIVE`` accepts the
    usual truthy/falsy spellings so launchers can switch the bus
    pod-wide without touching per-worker argv; ``TPUDIST_LIVE_ENDPOINT``
    is how the launcher tells every worker where the coordinator's
    aggregator listens (``[tcp://|udp://]host:port``) — without it a
    single-host run loops back over an ephemeral loopback port, which
    exercises the same socket path a pod does."""
    mode = cfg.live
    if mode is None:
        raw = (os.environ.get("TPUDIST_LIVE") or "off").lower()
        mode = "off" if raw in ("", "off", "0", "false", "no") else "on"
    if mode not in LIVE_MODES:
        raise ValueError(
            f"--live must be one of {LIVE_MODES}, got {mode!r}")
    port = cfg.live_port
    if port < 0:
        raise ValueError(f"--live-port must be >= 0, got {port}")
    if port == 0:
        env = _env_float("TPUDIST_LIVE_PORT")
        port = int(env) if env and env > 0 else 0
    endpoint = (cfg.live_endpoint
                or os.environ.get("TPUDIST_LIVE_ENDPOINT") or None)
    return mode == "on", port, endpoint


def flagship_model_config(max_seq_len: int = 512) -> ModelConfig:
    """BASELINE.json config #5: the synthetic Llama-block transformer
    (4 layers, 2048 hidden, 16 heads, SwiGLU 5504). Single source of truth
    for the headline benchmark and the driver compile-check entry."""
    return ModelConfig(name="transformer", vocab_size=32000, n_layers=4,
                       d_model=2048, n_heads=16, n_kv_heads=16, d_ff=5504,
                       max_seq_len=max_seq_len)


def parse_args(argv: Optional[Sequence[str]] = None) -> TrainConfig:
    """CLI → TrainConfig. Unknown flags are tolerated (parity with the
    reference's ``parse_known_args()[0]``), so launchers may pass extra
    flags without breaking the workload."""
    p = argparse.ArgumentParser(description="tpudist synthetic training workload")
    p.add_argument("--train-batch-size", type=int, default=64,
                   help="global batch size across all data-parallel replicas")
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--save-dir", type=str, default="ckpt")
    p.add_argument("--resume", nargs="?", const="latest", default=False,
                   choices=list(RESUME_MODES),
                   help="resume from the latest checkpoint in --save-dir: "
                        "bare/latest raises when the checkpoint cannot "
                        "drive this run; auto (the launcher's requeue "
                        "mode) prefers the committed elastic manifest, "
                        "falls back to orbax, and degrades a failed "
                        "restore to a flagged fresh start")
    p.add_argument("--ckpt-every-steps", type=int, default=0,
                   help="also checkpoint mid-epoch every N steps (0 = "
                        "epoch-end only); a preemption then loses at most "
                        "N steps")
    p.add_argument("--ckpt-sync", action="store_true",
                   help="synchronous checkpoint writes (async overlap is "
                        "the default)")
    p.add_argument("--ckpt-mode", type=str, default=None,
                   choices=list(CKPT_MODES),
                   help="checkpoint layout: orbax step dirs (default; "
                        "native gs:// support) or sharded — per-worker "
                        "shard files + an atomically committed "
                        "manifest.json (tpudist.elastic), resumable onto "
                        "a DIFFERENT process/device count (default: "
                        "$TPUDIST_CKPT_MODE, else orbax)")
    p.add_argument("--requeue-attempt", type=int, default=0,
                   help="which auto-requeue rerun this is (the launcher "
                        "passes it; lands in the kind=resume record and "
                        "the tpudist: resume line; default: "
                        "$TPUDIST_REQUEUE_ATTEMPT, else 0)")
    p.add_argument("--model", type=str, default="mlp",
                   choices=["mlp", "transformer", "moe"])
    p.add_argument("--dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--grad-accum-steps", type=int, default=1)
    p.add_argument("--adam-nu-dtype", type=str, default="float32",
                   choices=("float32", "bfloat16"),
                   help="Adam second-moment storage dtype; bfloat16 trades "
                        "~1e-3-relative update noise for halved nu HBM "
                        "traffic (big MoE optimizer states)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialise transformer layers in backward")
    p.add_argument("--xent-chunks", type=int, default=0,
                   help="stream the LM head + cross-entropy over N sequence "
                        "chunks instead of materialising full logits")
    p.add_argument("--lm-head", type=str, default="auto",
                   choices=("auto", "plain", "chunked", "fused"),
                   help="LM-head strategy; auto picks from the logits-pair"
                        " + activation HBM estimate (the default: the "
                        "operator never needs to know this flag exists)")
    p.add_argument("--fused-xent", action="store_true",
                   help="compute the LM head + cross-entropy with the fused "
                        "pallas kernel (logits never reach HBM); runs in "
                        "the pallas interpreter off-TPU")
    p.add_argument("--n-samples", type=int, default=2000)
    p.add_argument("--n-features", type=int, default=20)
    # transformer shape (defaults = BASELINE.json config #5: 4 layers, 2k hidden)
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=2048)
    p.add_argument("--n-heads", type=int, default=16)
    p.add_argument("--n-kv-heads", type=int, default=None)
    p.add_argument("--d-ff", type=int, default=5504)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--fsdp", type=int, default=1, help="fsdp mesh axis size")
    p.add_argument("--tensor", type=int, default=1, help="tensor mesh axis size")
    p.add_argument("--context", type=int, default=1, help="context mesh axis size")
    p.add_argument("--pipe", type=int, default=1,
                   help="pipeline mesh axis size (GPipe schedule over "
                        "transformer layer stages)")
    p.add_argument("--expert", type=int, default=1,
                   help="expert mesh axis size (MoE expert parallelism)")
    p.add_argument("--pp-microbatches", type=int, default=0,
                   help="pipeline microbatches per step (0 = pipe size)")
    p.add_argument("--pipeline-interleave", type=int, default=0,
                   help="virtual pipeline stages per device: v>1 runs "
                        "the interleaved schedule (each device holds v "
                        "round-robin layer chunks), cutting the bubble "
                        "from (S-1)/(M+S-1) to (S-1)/(v*M+S-1); "
                        "requires n-layers divisible by pipe*v and "
                        "microbatches divisible by pipe (default: "
                        "$TPUDIST_PIPELINE_INTERLEAVE, else 1 = GPipe)")
    p.add_argument("--grad-overlap", type=str, default=None,
                   choices=list(GRAD_OVERLAP_MODES),
                   help="DP gradient all-reduce schedule "
                        "(tpudist.parallel.overlap): off = trailing-"
                        "barrier baseline (reduce after the whole "
                        "backward), bucketed = size-bounded buckets "
                        "dispatched as backward produces them, hidden "
                        "behind the remaining backward compute; "
                        "bitwise-identical loss either way (default: "
                        "$TPUDIST_GRAD_OVERLAP, else off)")
    p.add_argument("--grad-bucket-mb", type=float, default=None,
                   help="bucket size bound for --grad-overlap bucketed "
                        "(default: $TPUDIST_GRAD_BUCKET_MB, else 4)")
    p.add_argument("--cross-slice", type=str, default=None,
                   choices=list(CROSS_SLICE_MODES),
                   help="cross-slice DP reduce schedule "
                        "(tpudist.parallel.overlap): flat = full "
                        "gradient bytes over DCN, hierarchical = "
                        "reduce-scatter in-slice (ICI) + all-reduce of "
                        "the 1/slice_size shard across slices (DCN) + "
                        "all-gather in-slice — cuts DCN bytes by the "
                        "slice size; bitwise-identical loss either way "
                        "(default: $TPUDIST_CROSS_SLICE, else flat)")
    p.add_argument("--cp-impl", type=str, default="ring",
                   choices=list(CP_IMPLS),
                   help="context-parallel attention: kv ring rotation "
                        "(zigzag causal balance, scales past head count) "
                        "or ulysses all-to-all head resharding")
    # moe shape
    p.add_argument("--n-experts", type=int, default=8)
    p.add_argument("--expert-top-k", type=int, default=2)
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--router-aux-weight", type=float, default=0.01)
    p.add_argument("--moe-group-size", type=int, default=4096,
                   help="tokens per routing group (bounds dispatch-tensor "
                        "memory; must divide batch*seq or routing falls "
                        "back to one global group)")
    p.add_argument("--fail-at", type=int, default=None,
                   help="fault injection: fail after this epoch (replaces the "
                        "reference's commented-out sys.exit(1), train.py:129)")
    p.add_argument("--chaos", type=str, default=None,
                   help="scripted fault-injection plan (tpudist.chaos): "
                        "';'-separated <fault>@<epoch>:<step>[:<rank>]"
                        "[,k=v...] events, fault one of kill | hang | "
                        "slow | corrupt_shard | torn_manifest | fs_error "
                        "| telemetry_garbage — e.g. "
                        "'corrupt_shard@0:6,mode=flip;kill@0:7' "
                        "(default: $TPUDIST_CHAOS, else off)")
    p.add_argument("--log-every", type=int, default=100)
    p.add_argument("--steps-per-dispatch", type=int, default=0,
                   help="superstep length: compile k train steps into one "
                        "lax.scan dispatch (one host fence per k steps). "
                        "0 = auto: largest divisor of --log-every/"
                        "--ckpt-every-steps up to 32, or 1 under "
                        "profiling/--fail-at/--log-every 1")
    p.add_argument("--staging-budget-mb", type=float, default=None,
                   help="per-device MB of device memory for staging epoch "
                        "batches; epochs over budget stream in "
                        "double-buffered k-step slabs overlapped with "
                        "compute (default: $TPUDIST_STAGING_BUDGET_MB, "
                        "else auto from device memory stats minus the "
                        "params/opt-state estimate)")
    p.add_argument("--stall-timeout-s", type=float, default=None,
                   help="flight-recorder watchdog: no step progress for "
                        "this long dumps thread stacks + memory stats + "
                        "last-N metrics to flightrec.worker<i> before the "
                        "launcher kills the job (default: "
                        "$TPUDIST_STALL_TIMEOUT_S, else 300; 0 disables "
                        "the watchdog, beacon stays on)")
    p.add_argument("--heartbeat-dir", type=str, default=None,
                   help="directory for heartbeat.worker<i> beacons and "
                        "flightrec.worker<i> dumps (default: "
                        "$TPUDIST_HEARTBEAT_DIR, else --save-dir)")
    p.add_argument("--hbm-sample-s", type=float, default=None,
                   help="HBM watermark sampler period in seconds; the "
                        "high-water mark lands in the kind=timing record "
                        "(default: $TPUDIST_HBM_SAMPLE_S, else 2.0; "
                        "0 disables)")
    p.add_argument("--autotune", type=str, default=None,
                   choices=list(AUTOTUNE_MODES),
                   help="measured-probe autotuning of the dispatch/"
                        "staging/remat operating point (tpudist.tune): "
                        "probe = short on-device trials before the timed "
                        "run (cached by workload fingerprint; the second "
                        "run costs zero probes), cache-only = reuse a "
                        "prior measurement but never probe (default: "
                        "$TPUDIST_AUTOTUNE, else off)")
    p.add_argument("--autotune-cache-dir", type=str, default=None,
                   help="tuning-cache directory (default: "
                        "$TPUDIST_AUTOTUNE_CACHE_DIR, else "
                        "<save-dir>/tune)")
    p.add_argument("--autotune-trials", type=int, default=0,
                   help="probe-trial budget for the autotune search "
                        "(0 = $TPUDIST_AUTOTUNE_TRIALS, else 12)")
    p.add_argument("--profile-dir", type=str, default=None,
                   help="write jax.profiler traces (tensorboard format) "
                        "here — EVERY worker captures, into "
                        "profile/worker<i> subdirs, so multi-host "
                        "traces are complete; the reference had no "
                        "profiling at all (SURVEY.md §5.1)")
    p.add_argument("--profile-window", type=int, default=0,
                   help="capture N mid-run supersteps with jax.profiler "
                        "on every worker (profile/worker<i> under "
                        "--trace-dir) and ingest the device timeline at "
                        "run end: kind=devtime record, device tracks in "
                        "pod_trace.json, comm_status verdict (default: "
                        "$TPUDIST_PROFILE_WINDOW, else 0 = off; "
                        "--profile-dir wins when both are set)")
    p.add_argument("--trace", type=str, default=None,
                   choices=list(TRACE_MODES),
                   help="host-side span tracing (obs.trace): on by "
                        "default (~1 µs/span); run end writes "
                        "trace.worker<i>.json per process and a merged "
                        "Perfetto pod_trace.json on the coordinator "
                        "(default: $TPUDIST_TRACE, else on)")
    p.add_argument("--trace-dir", type=str, default=None,
                   help="directory for trace.worker<i>.json / "
                        "pod_trace.json (default: $TPUDIST_TRACE_DIR, "
                        "else --save-dir)")
    p.add_argument("--live", type=str, default=None,
                   choices=list(LIVE_MODES),
                   help="live telemetry bus (obs.live): non-blocking "
                        "per-worker emitters stream records + heartbeats "
                        "to a coordinator aggregator that runs the "
                        "on-line alert engine over the SAME thresholds "
                        "as the exit verdict (tpudist.rules), rewrites "
                        "live_status.json, and serves Prometheus "
                        "/metrics (default: $TPUDIST_LIVE, else off)")
    p.add_argument("--live-port", type=int, default=0,
                   help="Prometheus exporter port on the coordinator "
                        "(/metrics, /status.json, /healthz; default: "
                        "$TPUDIST_LIVE_PORT, else an ephemeral port)")
    p.add_argument("--live-endpoint", type=str, default=None,
                   help="ingest endpoint workers ship records to "
                        "([tcp://|udp://]host:port; default: "
                        "$TPUDIST_LIVE_ENDPOINT, else the coordinator "
                        "binds loopback on an ephemeral port — the "
                        "launcher passes the coordinator's reachable "
                        "address on pods)")
    args = p.parse_known_args(argv)[0]

    return TrainConfig(
        batch_size=args.train_batch_size,
        epochs=args.epochs,
        lr=args.lr,
        seed=args.seed,
        save_dir=args.save_dir,
        resume=args.resume,
        ckpt_every_steps=args.ckpt_every_steps,
        ckpt_sync=args.ckpt_sync,
        ckpt_mode=args.ckpt_mode,
        requeue_attempt=args.requeue_attempt,
        grad_accum_steps=args.grad_accum_steps,
        adam_nu_dtype=args.adam_nu_dtype,
        dtype=args.dtype,
        remat=args.remat,
        xent_chunks=args.xent_chunks,
        fused_xent=args.fused_xent,
        lm_head=args.lm_head,
        pp_microbatches=args.pp_microbatches,
        pipeline_interleave=args.pipeline_interleave,
        cp_impl=args.cp_impl,
        grad_overlap=args.grad_overlap,
        grad_bucket_mb=args.grad_bucket_mb,
        cross_slice=args.cross_slice,
        fail_at=args.fail_at,
        chaos=args.chaos,
        log_every=args.log_every,
        profile_dir=args.profile_dir,
        profile_window=args.profile_window,
        steps_per_dispatch=args.steps_per_dispatch,
        staging_budget_mb=args.staging_budget_mb,
        stall_timeout_s=args.stall_timeout_s,
        heartbeat_dir=args.heartbeat_dir,
        hbm_sample_s=args.hbm_sample_s,
        autotune=args.autotune,
        autotune_cache_dir=args.autotune_cache_dir,
        autotune_trials=args.autotune_trials,
        trace=args.trace,
        trace_dir=args.trace_dir,
        live=args.live,
        live_port=args.live_port,
        live_endpoint=args.live_endpoint,
        data=DataConfig(n_samples=args.n_samples, n_features=args.n_features,
                        seed=args.seed),
        model=ModelConfig(name=args.model, n_features=args.n_features,
                          vocab_size=args.vocab_size, n_layers=args.n_layers,
                          d_model=args.d_model, n_heads=args.n_heads,
                          n_kv_heads=(args.n_kv_heads if args.n_kv_heads
                                      is not None else args.n_heads),
                          d_ff=args.d_ff, max_seq_len=args.seq_len,
                          n_experts=args.n_experts,
                          expert_top_k=args.expert_top_k,
                          capacity_factor=args.capacity_factor,
                          router_aux_weight=args.router_aux_weight,
                          moe_group_size=args.moe_group_size),
        parallel=ParallelConfig(pipe=args.pipe, fsdp=args.fsdp,
                                expert=args.expert, tensor=args.tensor,
                                context=args.context),
    )
