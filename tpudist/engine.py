"""Training engine — the DeepSpeed-engine equivalent, TPU-native.

Reference counterpart: ``deepspeed.initialize`` + ``model_engine.backward()``
/ ``.step()`` (reference ``train.py:87-93,113-114``), where the gradient
all-reduce is hidden inside the engine. Here the engine is a pytree
(``TrainState``) plus ONE compiled function:

  * **DP path (shard_map)** — when only the ``data`` mesh axis is >1, the
    train step is ``shard_map``-ped with an explicit
    ``lax.psum(grads, 'data')``: the collective under test is visible in the
    program, exactly what a fabric acceptance test wants.
  * **General path (jit + shardings)** — FSDP/tensor layouts annotate params
    with PartitionSpecs and let XLA's SPMD partitioner insert all-gathers /
    reduce-scatters / psums (the scaling-book recipe); no hand-written
    collectives to get wrong.

Context- and pipeline-parallel meshes build their loss through the
models' shard_map-based builders (make_cp_loss_fn, parallel.pipeline)
inside the general path. Both engine paths produce bitwise-identical math
on the same mesh ordering for the dense models (the MoE's group-local
routing is the documented exception, models/moe.py); tests assert
DP-vs-single-device and FSDP-vs-DP agreement.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from tpudist.config import TrainConfig
from tpudist.models import model_for
from tpudist.parallel import sharding as shd
from tpudist.scopes import scope
from tpudist.utils import compat


class TrainState(NamedTuple):
    step: jax.Array          # int32 global step counter
    params: Any
    opt_state: Any


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Adam, parity with ``torch.optim.Adam(lr)`` (reference train.py:85).

    Under mixed precision the FIRST moment is stored bf16 (optax
    ``mu_dtype`` — the standard low-precision-optimizer-state trade; the
    variance stays f32 for dynamic range): at the flagship shape the mu
    buffer halves, ~0.54 GB of HBM the step no longer stores or streams.
    f32 runs keep exact parity with the reference trajectory.

    ``--adam-nu-dtype bfloat16`` additionally stores the SECOND moment
    bf16 with STOCHASTIC rounding at store (opt-in; see
    :func:`_stochastic_round_bf16` — nearest-rounding would freeze the
    EMA, whose per-step relative change is below the bf16 half-ulp).
    The win is HBM traffic on big optimizer states: ~2.7 GB/step off
    the MoE model's 674M-param nu read+write (~3 ms/step on v5e,
    DESIGN.md MoE account). The update math runs in f32 either way:
    moments are upcast at use, rounded only at store; trajectory
    agreement and EMA-decay tracking are pinned in
    tests/test_engine.py."""
    mu_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else None
    if cfg.adam_nu_dtype == "bfloat16":
        return _adam_low_precision_nu(cfg.lr, mu_dtype=mu_dtype)
    return optax.adam(cfg.lr, mu_dtype=mu_dtype)


def _stochastic_round_bf16(x: jax.Array, count: jax.Array,
                           salt: int) -> jax.Array:
    """f32 → bf16 with STOCHASTIC rounding: add uniform dither in
    [0, ulp) to the low 16 mantissa bits, then truncate. Unbiased —
    E[sr(x)] = x — which is what makes a bf16-stored EMA work at all:
    round-to-NEAREST freezes the second moment once its per-step relative
    change (1−b2 = 1e-3) drops below the bf16 half-ulp (~2e-3), so nu
    ratchets to its historical max and the effective step size never
    recovers (r5 review finding). With SR the sub-ulp updates land with
    probability proportional to their size, so the EMA tracks in
    expectation — the same reason TPUs do hardware SR for low-precision
    accumulation.

    The dither is an integer HASH of (flat element index, step count,
    per-leaf salt) — murmur-style multiply/xor-shift mixing — NOT a
    threefry PRNG: counter-based jax.random.bits over the 674M-element
    MoE state measured ~10 ms/step, eating the ~3 ms the bf16 store
    saves (r5 measured). Rounding dither needs uniformity and
    step-decorrelation, not cryptographic strength; the EMA-decay test
    (tests/test_engine.py) pins that this hash's dither actually lets
    the moment track."""
    u32 = lambda v: jnp.uint32(v)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, x.size).reshape(x.shape)
    h = idx * u32(0x9E3779B1) + count.astype(jnp.uint32) * u32(0x85EBCA6B) \
        + u32(salt * 0xC2B2AE35 & 0xFFFFFFFF)
    h = h ^ (h >> 15)
    h = h * u32(0x27D4EB2F)
    h = h ^ (h >> 13)
    noise = h >> 16                      # 16 uniform dither bits
    bits = (bits + noise) & u32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(
        jnp.bfloat16)


def _adam_low_precision_nu(lr: float, *, b1: float = 0.9, b2: float = 0.999,
                           eps: float = 1e-8,
                           mu_dtype=None) -> optax.GradientTransformation:
    """optax.adam with the second moment STORED bf16 (optax exposes only
    ``mu_dtype``). Same math in f32 — decay, bias correction, rsqrt —
    with nu stochastically rounded to bf16 at store (see
    :func:`_stochastic_round_bf16` for why nearest-rounding is wrong
    here) and upcast at use. The SR dither hashes (element index, step
    count, leaf index), so the update stays a pure function of
    (state, grads)."""

    def init(params):
        mu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype), params)
        nu = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.bfloat16), params)
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32), mu=mu, nu=nu)

    def update(grads, state, params=None):
        count = state.count + 1
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                          f32(state.mu), f32(grads))
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          f32(state.nu), f32(grads))
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        updates = jax.tree.map(
            lambda m, v: -lr * (m / c1) / (jnp.sqrt(v / c2) + eps), mu, nu)
        mu_store = jax.tree.map(
            lambda x: x.astype(mu_dtype) if mu_dtype else x, mu)
        leaves, treedef = jax.tree.flatten(nu)
        nu_store = jax.tree.unflatten(treedef, [
            _stochastic_round_bf16(leaf, count, i)
            for i, leaf in enumerate(leaves)])
        return updates, optax.ScaleByAdamState(
            count=count, mu=mu_store, nu=nu_store)

    return optax.GradientTransformation(init, update)


def _compute_dtype(cfg: TrainConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _device_hbm_bytes() -> float:
    """Per-device accelerator memory for the head policy. Env override
    TPUDIST_HBM_BYTES (tests pin it for determinism), else the backend's
    reported limit, else a 16 GB v5e-class default (CPU backends report
    no limit; the policy then errs toward the plain head at test shapes,
    which is what the CPU reference path wants)."""
    import os
    env = os.environ.get("TPUDIST_HBM_BYTES")
    if env:
        return float(env)
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return float(stats["bytes_limit"])
    except Exception:
        pass
    return 16e9


def _resolve_lm_head(cfg: TrainConfig,
                     mesh: Mesh | None) -> tuple[bool, int]:
    """cfg.lm_head -> concrete (fused_xent, xent_chunks) for this run.

    ``auto`` (the default) honors an explicit --fused-xent/--xent-chunks,
    else asks models.transformer.pick_lm_head with per-DEVICE head tokens
    (the logits live batch/fsdp/context-sharded) and an analytic train-
    state estimate (f32 master + mu/nu at their configured storage
    dtypes: 12 B/param full-f32 down to 8 B with bf16 mu and nu) —
    analytic rather than memory_stats so the decision does not depend on
    whether init_state already materialised the state."""
    if cfg.lm_head != "auto":
        # a forced mode with a CONTRADICTORY explicit flag is a config
        # error (a stale --fused-xent in a launch script must not be
        # silently dropped), not a precedence question
        if cfg.lm_head == "plain" and (cfg.fused_xent or cfg.xent_chunks):
            raise ValueError(
                "--lm-head plain contradicts --fused-xent/--xent-chunks")
        if cfg.lm_head == "fused" and cfg.xent_chunks:
            raise ValueError("--lm-head fused contradicts --xent-chunks")
        if cfg.lm_head == "chunked" and cfg.fused_xent:
            raise ValueError("--lm-head chunked contradicts --fused-xent")
    if cfg.lm_head == "plain":
        return False, 0
    if cfg.lm_head == "fused":
        return True, 0
    if cfg.lm_head == "chunked":
        return False, cfg.xent_chunks or 4
    if cfg.lm_head != "auto":
        raise ValueError(f"unknown --lm-head {cfg.lm_head!r}")
    if cfg.fused_xent or cfg.xent_chunks:
        return cfg.fused_xent, cfg.xent_chunks
    return _auto_lm_head(cfg, mesh)


def _auto_lm_head(cfg: TrainConfig, mesh: Mesh | None) -> tuple[bool, int]:
    """The auto policy pick, logged at rank 0 — here, inside the single
    source of truth, not re-derived at call sites (r5 review). Dedup is
    once per resolved CHOICE per process: make_loss_fn runs at least
    twice per run (train + eval), and a repeat of the same line carries
    no information; a changed choice always prints."""
    from tpudist.models import transformer as T
    m = cfg.model
    batch_shards = 1 if mesh is None else (
        mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
    ctx = 1 if mesh is None else mesh.shape.get("context", 1)
    n_tok = (max(cfg.batch_size // max(batch_shards, 1), 1)
             * max(m.max_seq_len // max(ctx, 1), 1))
    hd = m.d_model // m.n_heads
    attn = 2 * m.d_model * m.d_model + 2 * m.d_model * m.n_kv_heads * hd
    ffn = 3 * m.d_model * m.d_ff
    expert_mult = m.n_experts if m.name == "moe" else 1
    # per-device state share: fsdp and tensor shard every param's storage;
    # the expert axis additionally shards the (n_experts×) FFN weights
    wshards = 1 if mesh is None else (
        mesh.shape.get("fsdp", 1) * mesh.shape.get("tensor", 1))
    eshards = 1 if mesh is None else mesh.shape.get("expert", 1)
    n_params_dev = (m.vocab_size * m.d_model
                    + m.n_layers * attn
                    + m.n_layers * ffn * expert_mult
                    / max(eshards, 1)) / max(wshards, 1)
    # f32 master (4) + mu (bf16 under mixed precision, else f32) + nu
    # (bf16 when --adam-nu-dtype says so, else f32)
    state_bytes_per_param = (4 + (2 if cfg.dtype == "bfloat16" else 4)
                             + (2 if cfg.adam_nu_dtype == "bfloat16" else 4))
    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    fused_xent, xent_chunks = T.pick_lm_head(
        n_tok, m.vocab_size, m.d_model, m.n_layers, dtype_bytes,
        n_params_dev * state_bytes_per_param,
        _device_hbm_bytes())
    choice = ("fused" if fused_xent
              else f"chunked({xent_chunks})" if xent_chunks else "plain")
    if choice not in _AUTO_HEAD_LOGGED:
        _AUTO_HEAD_LOGGED.add(choice)
        from tpudist.metrics import log0
        log0(f"tpudist: --lm-head auto -> {choice}")
    return fused_xent, xent_chunks


_AUTO_HEAD_LOGGED: set = set()


def make_loss_fn(cfg: TrainConfig, mesh: Mesh | None = None, *,
                 constrain_logits: bool = False) -> Callable:
    """(params, batch) -> scalar loss, for the configured model.

    With a mesh whose ``context`` axis is >1, any model providing
    ``make_cp_loss_fn`` (transformer, moe) runs context-parallel —
    sequence sharded, ring or ulysses attention per ``cfg.cp_impl``.

    ``constrain_logits`` is only legal (and only needed) under the
    jit+shardings train path — a NamedSharding constraint inside the
    fully-manual shard_map DP path is an error."""
    model = model_for(cfg.model)
    dt = _compute_dtype(cfg)
    if (mesh is not None and mesh.shape.get("expert", 1) > 1
            and cfg.model.name != "moe"):
        # without expert-sharded weights the axis silently replicates all
        # compute — half the slice doing duplicate work is a config error
        raise ValueError(f"--expert > 1 requires --model moe; "
                         f"{cfg.model.name!r} has no expert-sharded params")
    if cfg.model.name == "mlp":
        if mesh is not None and mesh.shape.get("pipe", 1) > 1:
            raise ValueError("pipeline parallelism requires a layered "
                             "model (transformer/moe), not mlp")
        return functools.partial(model.loss_fn, dtype=dt)

    fused_xent, xent_chunks = _resolve_lm_head(cfg, mesh)
    pp = mesh is not None and mesh.shape.get("pipe", 1) > 1
    cp = mesh is not None and mesh.shape.get("context", 1) > 1
    if pp:
        if cp:
            raise ValueError(
                "pipe and context parallelism both manualize their own "
                "mesh axis in a shard_map and do not compose; pick one")
        from tpudist.config import resolve_pipeline_interleave
        from tpudist.parallel.pipeline import make_pp_loss_fn
        pp_loss = make_pp_loss_fn(cfg.model, mesh,
                                  n_microbatches=cfg.pp_microbatches,
                                  dtype=dt, remat=cfg.remat,
                                  xent_chunks=xent_chunks,
                                  fused_xent=fused_xent,
                                  interleave=resolve_pipeline_interleave(
                                      cfg))

        def loss(params, batch):
            tokens = batch[0] if isinstance(batch, tuple) else batch
            return pp_loss(params, tokens)
        return loss
    if cp:
        if not hasattr(model, "make_cp_loss_fn"):
            raise ValueError(
                f"context parallelism is not implemented for model "
                f"{cfg.model.name!r}")
        cp_loss = model.make_cp_loss_fn(cfg.model, mesh, dtype=dt,
                                        remat=cfg.remat,
                                        xent_chunks=xent_chunks,
                                        fused_xent=fused_xent,
                                        impl=cfg.cp_impl)

        def loss(params, batch):
            tokens = batch[0] if isinstance(batch, tuple) else batch
            return cp_loss(params, tokens)
        return loss

    logits_sh = None
    if mesh is not None and constrain_logits:
        # Batch dims follow the batch layout; the vocab dim rides the tensor
        # axis so the tied-head backward (dE = dlogitsᵀ·h, vocab-sharded
        # embed grad) consumes dlogits natively — without this the
        # partitioner demands a batch→vocab reshard of the (b,s,v) cotangent
        # it can only satisfy by full rematerialisation (dp+fsdp+tensor).
        vocab_axis = ("tensor" if cfg.model.vocab_size
                      % mesh.shape.get("tensor", 1) == 0 else None)
        logits_sh = NamedSharding(
            mesh, P(("data", "fsdp"), None, vocab_axis))

    def loss(params, batch):
        tokens = batch[0] if isinstance(batch, tuple) else batch
        return model.loss_fn(params, tokens, cfg.model, dtype=dt,
                             remat=cfg.remat, xent_chunks=xent_chunks,
                             fused_xent=fused_xent,
                             logits_sharding=logits_sh)
    return loss


def init_state(key: jax.Array, cfg: TrainConfig,
               mesh: Mesh | None = None) -> TrainState:
    """Init params + opt state, placed into their sharded layout if a mesh is
    given. Init is seeded → deterministic across process counts (the
    convergence oracle depends on this; SURVEY.md §7 "hard parts")."""
    model = model_for(cfg.model)
    params = model.init(key, cfg.model)
    tx = make_optimizer(cfg)
    opt_state = tx.init(params)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt_state)
    if mesh is not None:
        state = jax.device_put(state, state_shardings(cfg, mesh))
    return state


def state_shardings(cfg: TrainConfig, mesh: Mesh) -> TrainState:
    """NamedShardings for the full TrainState. Opt-state moments share the
    params' layout (ZeRO-style: optimizer state lives where the shard
    lives); scalar leaves are replicated."""
    model = model_for(cfg.model)
    params_shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg.model))
    # drop axes that don't divide a dim (vocab 97 over fsdp=2 → replicated)
    pspecs = shd.sanitize_specs(params_shape, model.param_specs(cfg.model),
                                mesh)
    psh = shd.named(mesh, pspecs)
    # optax adam state is a tuple of states where mu/nu are params-shaped
    # pytrees; those subtrees get the params' layout (ZeRO-style: optimizer
    # state lives with the shard), everything else is replicated.
    params_struct = jax.tree.structure(psh)
    tx = make_optimizer(cfg)
    opt_shape = jax.eval_shape(tx.init, params_shape)
    # Walk the opt-state shape; replace params-shaped subtrees with psh.
    opt_sh = _match_subtrees(opt_shape, params_struct, psh, mesh)
    return TrainState(step=NamedSharding(mesh, P()), params=psh,
                      opt_state=opt_sh)


def _match_subtrees(shape_tree, params_struct, psh, mesh):
    """Replace every params-structured subtree of an optax state shape with
    the params shardings; replicate everything else."""
    def rec(node):
        try:
            if jax.tree.structure(node) == params_struct:
                return psh
        except Exception:
            pass
        if isinstance(node, tuple) and not hasattr(node, "shape"):
            out = tuple(rec(c) for c in node)
            return type(node)(*out) if hasattr(node, "_fields") else out
        if isinstance(node, list):
            return [rec(c) for c in node]
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return NamedSharding(mesh, P())
    return rec(shape_tree)


def _microbatch(loss_fn, params, batch, n_accum: int):
    """Gradient accumulation via lax.scan over microbatches (the reference
    configured accumulation off, train.py:80; we support it properly)."""
    if n_accum == 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def split(x):
        return x.reshape(n_accum, x.shape[0] // n_accum, *x.shape[1:])
    micro = jax.tree.map(split, batch)

    def body(carry, mb):
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        acc_loss, acc_g = carry
        return (acc_loss + loss,
                jax.tree.map(jnp.add, acc_g, grads)), None
    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(body, zero, micro)
    inv = 1.0 / n_accum
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def _build_step_body(cfg: TrainConfig, mesh: Mesh):
    """The shared single-step body behind :func:`make_train_step` and
    :func:`make_superstep`: ``(TrainState, batch) -> (TrainState, loss)``.

    Returns ``(body, dp, st_sh)``: ``dp`` True selects the explicit-psum
    shard_map path (pure-DP meshes — the body then contains the visible
    gradient all-reduce and must trace inside a fully-manual shard_map);
    otherwise the body carries the jit+shardings path's constraint
    annotations and ``st_sh`` holds the TrainState's NamedShardings.
    """
    tx = make_optimizer(cfg)
    dp = shd.pure_dp(mesh)
    # the logits constraint belongs to the jit+shardings path only — inside
    # the shard_map DP body every mesh axis is manual and a NamedSharding
    # constraint is rejected at trace time
    model_loss = make_loss_fn(cfg, mesh, constrain_logits=not dp)

    def loss_fn(params, batch):
        # forward ops trace under jvp(loss), backward under
        # transpose(jvp(loss)): how a capture tells the two apart
        with scope("loss"):
            return model_loss(params, batch)

    st_sh = None if dp else state_shardings(cfg, mesh)
    from tpudist.config import resolve_cross_slice, resolve_grad_overlap
    overlap_mode, bucket_bytes = resolve_grad_overlap(cfg)
    if overlap_mode != "off" and not dp:
        if any(int(s) > 1 for s in mesh.devices.shape):
            # the bucketed schedule rewrites the PROGRAM's explicit
            # psums; on jit+shardings meshes the gradient reduction is
            # inserted by the partitioner and there is nothing
            # program-level to re-schedule — a silently-inert flag
            # would fake the acceptance signal, so refuse loudly
            raise ValueError(
                f"--grad-overlap {overlap_mode} requires the explicit-"
                f"collective pure-DP mesh (only the 'data' axis > 1); "
                f"this mesh routes gradients through the jit+shardings "
                f"partitioner")
        # a single-device mesh has no all-reduce at all: the flag is
        # inert (a laptop dry-run of a pod launch script must not crash)
        overlap_mode = "off"
    cross_mode = resolve_cross_slice(cfg)
    slice_groups = None
    if cross_mode == "hierarchical" and not dp:
        if any(int(s) > 1 for s in mesh.devices.shape):
            # same refusal logic as --grad-overlap: the ladder rewrites
            # explicit psums, and the jit+shardings partitioner owns the
            # gradient reduce on non-DP meshes
            raise ValueError(
                f"--cross-slice hierarchical requires the explicit-"
                f"collective pure-DP mesh (only the 'data' axis > 1); "
                f"this mesh routes gradients through the jit+shardings "
                f"partitioner")
        cross_mode = "flat"
    if dp:
        from tpudist.parallel import mesh as mesh_lib
        slice_groups = mesh_lib.data_slice_groups(mesh)
        if cross_mode == "hierarchical" and slice_groups is None:
            # single slice: there is no DCN phase to shard, and lowering
            # the ladder anyway would emit dead in-slice scatter/gather
            # phases. Downgrade LOUDLY — tests and operators read this
            # line to know the program is the flat one.
            from tpudist.metrics import log0
            log0("tpudist: --cross-slice hierarchical downgraded to "
                 "flat: single-slice mesh (no cross-slice DCN phase to "
                 "shard)")
            cross_mode = "flat"

    def sgd_update(state: TrainState, loss, grads):
        with scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt), loss

    if dp:
        from tpudist.parallel import overlap as overlap_lib

        def body(state: TrainState, batch):
            loss, grads = _microbatch(loss_fn, state.params, batch,
                                      cfg.grad_accum_steps)
            # THE collective under test: gradient all-reduce over ICI/DCN
            # (reference equivalent: NCCL all-reduce inside
            # model_engine.backward(), train.py:113). The schedule is a
            # program property (parallel.overlap): "off" pins the
            # trailing-barrier baseline, "bucketed" chains size-bounded
            # per-bucket reduces behind the backward — bitwise-identical
            # math either way, only the exposed-comm fraction moves.
            grads = overlap_lib.grad_mean(grads, "data",
                                          mode=overlap_mode,
                                          bucket_bytes=bucket_bytes,
                                          cross=cross_mode,
                                          slice_groups=slice_groups)
            loss = lax.pmean(loss, "data")
            return sgd_update(state, loss, grads)
    else:
        def body(state: TrainState, batch):
            # Pin the weights to their layout *inside* the traced body: the
            # transpose of a sharding constraint constrains the cotangent,
            # so the scan-transpose gradient accumulation of the stacked
            # layer weights keeps the params' sharding instead of letting
            # the partitioner pick one it then can't reconcile
            # (spmd_partitioner "involuntary full rematerialization" on the
            # grad add_any).
            params = jax.lax.with_sharding_constraint(state.params,
                                                      st_sh.params)
            loss, grads = _microbatch(loss_fn, params, batch,
                                      cfg.grad_accum_steps)
            grads = jax.lax.with_sharding_constraint(grads, st_sh.params)
            return sgd_update(state, loss, grads)
    return body, dp, st_sh


class OnMesh:
    """A jitted program that is always traced, lowered and dispatched
    inside its mesh's context (``jax.set_mesh``). The context is what a
    kernel that GSPMD cannot partition reads to run per shard
    (models.transformer._flash_per_shard), and it is part of jit's trace
    key — so every entry to the program goes through here, the run-end
    ``.lower()`` hooks included, and one program stays one trace."""

    def __init__(self, jitted, mesh: Mesh):
        self.jitted, self.mesh = jitted, mesh

    def __call__(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jitted(*args)

    def lower(self, *args):
        with jax.set_mesh(self.mesh):
            return self.jitted.lower(*args)


def _arg_specs(args):
    """Shape/dtype/sharding skeletons of a call's arguments — what
    ``jit.lower`` needs, WITHOUT keeping any buffer alive (holding the
    last staged slab would break the streaming pipeline's ≤2-resident
    guarantee; donated states are deleted but their avals survive).
    Only NamedShardings are kept: host-created scalars (lo, hi)
    carry a SingleDeviceSharding that would contradict the mesh-wide
    state at lowering — the real call passes them uncommitted and the
    specs must reproduce that."""
    def spec(a):
        sh = getattr(a, "sharding", None)
        if not isinstance(sh, NamedSharding):
            sh = None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    return jax.tree.map(spec, args)


def _cost_analysis_hook(jitted, cell) -> Callable:
    """Build the ``.cost_analysis()`` accessor attached to the step /
    superstep callables: XLA's cost properties (flops, bytes accessed)
    of the EXACT program the run dispatched (tpudist.obs.mfu reads this
    for the run-end roofline record). ``cell[0]`` holds the first call's
    arg specs. Lowering + compiling here is off the step path, runs at
    most once per run, and hits the persistent compilation cache when
    one is configured; any failure degrades to None — observability
    must never fail a run."""
    def cost_analysis():
        if cell[0] is None:
            return None
        try:
            return jitted.lower(*cell[0]).compile().cost_analysis()
        except Exception:
            return None
    return cost_analysis


def _memory_analysis_hook(jitted, cell) -> Callable:
    """Build the ``.memory_analysis()`` accessor attached beside
    ``.cost_analysis()``: XLA's memory plan for the EXACT program the
    run dispatched — argument/output/temp/generated-code bytes
    (tpudist.obs.memledger's program_temp bucket reads this). Same
    contract as the cost hook: lowering hits jit's trace cache after
    the first call; None before the first call, on backends without
    memory planning, or on any failure — observability must never fail
    a run."""
    def memory_analysis():
        if cell[0] is None:
            return None
        try:
            mem = compat.memory_analysis(
                jitted.lower(*cell[0]).compile())
            return mem or None
        except Exception:
            return None
    return memory_analysis


def _lowered_text_hook(jitted, cell) -> Callable:
    """Build the ``.lowered_text()`` accessor attached beside
    ``.cost_analysis()``: the StableHLO text of the EXACT program the
    run dispatched (obs.devtime.collective_bytes parses its collective
    ops into the per-fabric byte accounting the devtime record and the
    DCN-bytes gauge carry). Lowering hits jit's trace cache after the
    first call; None before the first call or on any failure —
    observability must never fail a run."""
    def lowered_text(debug_info: bool = False):
        # debug_info: with every op's location, i.e. its name stack
        # (tpudist.scopes) — what tests read the scopes from
        if cell[0] is None:
            return None
        try:
            return jitted.lower(*cell[0]).as_text(debug_info=debug_info)
        except Exception:
            return None
    return lowered_text


def make_train_step(cfg: TrainConfig, mesh: Mesh) -> Callable:
    """Build the compiled train step: (TrainState, batch) -> (TrainState, loss).

    Chooses the explicit-psum shard_map path for pure-DP meshes, else the
    jit+shardings path. Loss returned is the global mean. The returned
    callable exposes ``.cost_analysis()`` (compiled-program flops/bytes,
    None before the first call) for the observability layer.
    """
    body, dp, st_sh = _build_step_body(cfg, mesh)

    if dp:
        # --- DP path: shard_map with explicit gradient all-reduce ---
        def jitted(state, batch):
            # batch specs are built per-leaf (x is 2-D, labels are 1-D);
            # re-wrapping per trace is free — jit caches by structure.
            bspecs = jax.tree.map(lambda x: shd.batch_spec(x.ndim), batch)
            spmd = jax.shard_map(body, mesh=mesh, in_specs=(P(), bspecs),
                                 out_specs=(P(), P()), check_vma=False)
            return spmd(state, batch)
        # donate the incoming state like the general path does: the update
        # writes in place instead of carrying two copies of params+opt
        # state per step
        jitted = jax.jit(jitted, donate_argnums=(0,))
    else:
        # --- general path: jit + shardings, XLA inserts collectives ---
        jitted = jax.jit(body, in_shardings=(st_sh, None),
                         out_shardings=(st_sh, NamedSharding(mesh, P())),
                         donate_argnums=(0,))
    jitted = OnMesh(jitted, mesh)

    _specs: list = [None]

    def step(state, batch):
        staged = shd.put_batch(mesh, batch)
        if _specs[0] is None:
            _specs[0] = _arg_specs((state, staged))
        return jitted(state, staged)
    step.cost_analysis = _cost_analysis_hook(jitted, _specs)
    step.lowered_text = _lowered_text_hook(jitted, _specs)
    step.memory_analysis = _memory_analysis_hook(jitted, _specs)
    return step


def state_bytes_per_device(state) -> int:
    """Largest per-device byte footprint of a (possibly sharded) pytree —
    the params/opt-state term of the staging-budget estimate
    (config.resolve_staging_budget_bytes). Counted from each leaf's
    addressable shards so FSDP/TP layouts report their true per-device
    share while replicated leaves count in full."""
    per: dict = {}
    for leaf in jax.tree.leaves(state):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            n = getattr(leaf, "nbytes", 0)
            for d in jax.local_devices():
                per[d.id] = per.get(d.id, 0) + n // max(
                    jax.local_device_count(), 1)
            continue
        for sh in shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return max(per.values()) if per else 0


def make_superstep(cfg: TrainConfig, mesh: Mesh, k: int) -> Callable:
    """Compiled multi-step "superstep" dispatch:
    ``(TrainState, total, slab, lo, hi) -> (TrainState, total,
    per_step_losses)``.

    Wraps the same per-step body as :func:`make_train_step` in a
    ``lax.scan`` over the slab's leading (step) axis — ONE host dispatch
    and ONE fence per ``k`` steps instead of ``k`` of each, which is the
    whole game for the paper's deliberately dispatch-bound workload. The
    slab is a device-resident ``(k, local_batch, ...)`` pytree (staged by
    ``sharding.put_epoch``, whole-epoch or streamed slab-wise per
    ``sharding.plan_slabs``).

    The slab's step axis is always EXACTLY ``k`` long; ``lo``/``hi``
    bound the valid steps inside it (``lo <= idx < hi``). Steps outside
    the bounds are MASKED out via ``lax.cond``: the skip branch passes
    the carried state/total through untouched. ``cond`` rather than a
    ``where``-select on the outputs because a select makes the carried
    state a second consumer of the update arithmetic, which changes
    XLA's fusion (FMA contraction) of the Adam update on the CPU backend
    and costs the bitwise-parity guarantee at the ULP level (measured:
    3/64 weights off by 1 ULP after 8 steps); ``cond`` isolates the body
    in its own branch computation, so valid steps lower identically to
    the unmasked scan. One compiled program then serves every slab in
    the run — the zero-padded trailing partial superstep (``hi < k``)
    and the mid-epoch-resume realignment slab (``lo > 0``) included —
    where the old variable-length tail forced a second compile per
    epoch. ``lo``/``hi`` are traced scalars, so their values never
    recompile; ``superstep.traces`` counts actual retraces (tests pin
    it to 1).

    Donation contract (audited for the staging pipeline): the incoming
    ``state`` and ``total`` are donated — the update writes in place, so
    no second copy of params+opt state sits beside the staged slabs. The
    slab argument is deliberately NOT donated: no output of the scan
    shares its ``(k, batch, ...)`` shape, so XLA could never alias it
    (donation would only emit an unusable-donation warning per compile
    and free nothing early). Slab memory is reclaimed by reference
    death instead — each k-slice dies after its dispatch, and the
    streaming loop drops each staged slab as soon as its last superstep
    is dispatched, keeping at most two slabs resident.

    The carried ``total`` accumulates each valid step's global-mean loss
    in step order (``((total+l0)+l1)+…`` — the masked select returns the
    bitwise-identical sum for valid steps), so the epoch's running loss
    sum and the stdout ``Avg loss`` stay bitwise-identical to per-step
    dispatch. Per-step losses come back as a ``k``-vector; entries
    outside ``[lo, hi)`` are meaningless and must not be read.
    """
    if k < 1:
        raise ValueError(f"superstep length must be >= 1, got {k}")
    body, dp, st_sh = _build_step_body(cfg, mesh)
    traces: list = []

    def super_body(state, total, slab, lo, hi):
        traces.append(1)   # trace-time marker: one entry per compilation

        def scan_body(carry, xs):
            state, total = carry
            batch, idx = xs
            valid = (idx >= lo) & (idx < hi)

            def run(ops):
                state, total, batch = ops
                state, loss = body(state, batch)
                return state, total + loss, loss

            def skip(ops):
                state, total, _ = ops
                # emitted loss for masked steps is a placeholder; the
                # train loop never reads outside [lo, hi)
                return state, total, jnp.float32(0)

            state, total, loss = lax.cond(valid, run, skip,
                                          (state, total, batch))
            return (state, total), loss

        n = jax.tree.leaves(slab)[0].shape[0]
        (state, total), losses = lax.scan(
            scan_body, (state, total), (slab, jnp.arange(n)))
        return state, total, losses

    rep = NamedSharding(mesh, P())
    if dp:
        def jitted(state, total, slab, lo, hi):
            sspecs = jax.tree.map(lambda x: shd.epoch_spec(x.ndim), slab)
            spmd = jax.shard_map(super_body, mesh=mesh,
                                 in_specs=(P(), P(), sspecs, P(), P()),
                                 out_specs=(P(), P(), P()),
                                 check_vma=False)
            return spmd(state, total, slab, lo, hi)
        jitted = jax.jit(jitted, donate_argnums=(0, 1))
    else:
        jitted = jax.jit(super_body,
                         in_shardings=(st_sh, rep, None, None, None),
                         out_shardings=(st_sh, rep, rep),
                         donate_argnums=(0, 1))
    jitted = OnMesh(jitted, mesh)

    _specs: list = [None]

    def superstep(state, total, slab, lo, hi):
        # the carry comes back typed with the mesh, and jit keys its trace
        # on that: a caller's fresh off-mesh zero is placed here so the
        # first dispatch and every later one share one program (a no-op
        # for a carry that is already there)
        args = (state, jax.device_put(total, rep), slab, jnp.int32(lo),
                jnp.int32(hi))
        if _specs[0] is None:
            _specs[0] = _arg_specs(args)
        return jitted(*args)
    superstep.traces = traces
    superstep.cost_analysis = _cost_analysis_hook(jitted, _specs)
    superstep.lowered_text = _lowered_text_hook(jitted, _specs)
    superstep.memory_analysis = _memory_analysis_hook(jitted, _specs)
    return superstep


def make_eval_fn(cfg: TrainConfig, mesh: Mesh) -> Callable:
    """(state, batch) -> global mean loss, no update."""
    loss_fn = make_loss_fn(cfg, mesh)
    jitted = OnMesh(
        jax.jit(lambda state, batch: loss_fn(state.params, batch)), mesh)

    def ev(state, batch):
        return jitted(state, shd.put_batch(mesh, batch))
    return ev
