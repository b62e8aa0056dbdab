"""On-chip tests: Mosaic-compiled pallas kernels, bf16 numerics, train smoke.

These sizes are chosen to cover the hazards the interpreter hides:
unaligned token counts (undefined VMEM padding rows — the r1 dE bug),
vocab remainders, and the default block geometry's VMEM fit at the real
d_model=2048.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.pallas.fused_xent import fused_lm_head_xent


# ONE reference shared with the acceptance gate (tpudist.selfcheck) — a
# semantic fix must not fork between the lanes (r3 review finding)
from tpudist.ops.reference import lm_head_xent as _ref_loss  # noqa: E402


def _data(t, d, v, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(k1, (t, d), dtype)
    emb = jax.random.normal(k2, (v, d), dtype) * 0.02
    tgt = jax.random.randint(k3, (t,), 0, v)
    return h, emb, tgt


@pytest.mark.parametrize("t,v", [
    (512, 4096),     # aligned both dims
    (400, 4096),     # token remainder vs block_t=256 (the r1 dE hazard)
    (512, 5000),     # vocab remainder vs both block_v sizes
    (20000, 4096),   # 10 supergroups -> two outer dE-partial chunks (r4
                     # merged backward) + masked supergroup remainder
])
def test_fused_xent_compiled_matches_reference(t, v):
    """Body LIVES in tpudist.selfcheck (the acceptance gate) so the two
    lanes cannot drift — same rule as the flash checks below."""
    from tpudist import selfcheck
    selfcheck._check_fused_xent_shape(t, v)


def test_fused_xent_bf16_default_blocks_vmem_fit():
    """Bench geometry (d=2048, vocab 32000, default block sizes) must fit
    the chip's scoped VMEM in fwd AND both backward kernels — this exact
    configuration OOMed at block_v_bwd=1280/640 during r2 bring-up."""
    h, emb, tgt = _data(1024, 2048, 32000, dtype=jnp.bfloat16)
    loss, (gh, ge) = jax.value_and_grad(
        lambda h, e: fused_lm_head_xent(h, e, tgt), argnums=(0, 1))(h, emb)
    want = _ref_loss(h, emb, tgt)
    np.testing.assert_allclose(float(loss), float(want), rtol=5e-2)
    assert bool(jnp.isfinite(gh.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(ge.astype(jnp.float32)).all())


def test_transformer_fused_loss_matches_plain_on_chip():
    """bf16 end-to-end: the fused LM head and the whole-logits path agree
    on-chip (Mosaic vs XLA schedules)."""
    from tpudist import data as tdata
    from tpudist.config import ModelConfig
    from tpudist.models import transformer

    cfg = ModelConfig(name="transformer", vocab_size=2048, n_layers=2,
                      d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
                      max_seq_len=128)
    toks = tdata.make_synthetic_tokens(4, 129, cfg.vocab_size, seed=0)
    p = transformer.init(jax.random.PRNGKey(0), cfg)
    base = transformer.loss_fn(p, toks, cfg, dtype=jnp.bfloat16)
    fused = transformer.loss_fn(p, toks, cfg, dtype=jnp.bfloat16,
                                fused_xent=True)
    np.testing.assert_allclose(float(fused), float(base), rtol=2e-2)


def test_train_step_smoke_on_chip():
    """One real train step of the tiny transformer on the chip: finite loss,
    and a second step strictly decreases it (same batch)."""
    from tpudist import data as tdata, engine
    from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                                TrainConfig)
    from tpudist.parallel import build_mesh

    cfg = TrainConfig(
        batch_size=8, lr=1e-3, seed=0, dtype="bfloat16",
        data=DataConfig(n_samples=8),
        model=ModelConfig(name="transformer", vocab_size=512, n_layers=2,
                          d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
                          max_seq_len=64),
        parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = tdata.make_synthetic_tokens(8, 65, 512, seed=0)
    state, l0 = step(state, (toks,))
    state, l1 = step(state, (toks,))
    l0, l1 = float(l0), float(l1)
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0


@pytest.mark.parametrize("kv", [8, 2])
def test_flash_attention_compiled_matches_dense_on_chip(kv):
    """Mosaic-compiled flash attention vs the dense XLA path at the bench
    head geometry (hd=128), bf16, causal — fwd and all three grads; kv=2
    covers the grouped-query expansion + dk/dv group-sum on chip."""
    from tpudist.ops.pallas.flash_attention import flash_attention

    b, s, h, hd = 4, 512, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.bfloat16)

    from tpudist.ops.reference import dense_attention as dense

    got = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    want = jax.jit(dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)

    g_got = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        flash_attention(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda a, b_, c: jnp.vdot(
        dense(a, b_, c), ct).astype(jnp.float32),
        argnums=(0, 1, 2)))(q, k, v)
    for g, w, name in zip(g_got, g_want, "q k v".split()):
        # bf16 operands, values O(30): elementwise ULP-scale differences
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=0.5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("kv", [4, 2])
def test_flash_attention_long_context_on_chip(kv):
    """Multi-block Mosaic schedule (seq 2048 = 4 kv blocks); kv=2 compiles
    the in-kernel GQA _expand_rep/_group_sum under the accumulator
    schedule (r3 advisor: no on-chip coverage of multi-block GQA before
    this). The body LIVES in tpudist.selfcheck (the acceptance gate) so
    the two lanes cannot drift — same rule as _ref_loss above."""
    from tpudist import selfcheck
    selfcheck._check_flash_long(kv=kv)


def test_ring_flash_merge_on_chip():
    """The ring-attention hop merge compiled on chip: two disjoint-kv
    kernel calls merged via merge_partials equal one whole-kv call, fwd +
    grads (dlse folding) — the per-hop operation of the CP flash path.
    Body shared with the acceptance gate (tpudist.selfcheck)."""
    from tpudist import selfcheck
    selfcheck.check_ring_flash_merge()


def test_moe_train_step_smoke_on_chip():
    """MoE dispatch einsums + expert FFN compile and train on the chip."""
    from tpudist import data as tdata, engine
    from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                                TrainConfig)
    from tpudist.parallel import build_mesh

    cfg = TrainConfig(
        batch_size=8, lr=1e-3, seed=0, dtype="bfloat16",
        data=DataConfig(n_samples=8),
        model=ModelConfig(name="moe", vocab_size=512, n_layers=2,
                          d_model=128, n_heads=4, n_kv_heads=4, d_ff=128,
                          max_seq_len=64, n_experts=4, expert_top_k=2),
        parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = tdata.make_synthetic_tokens(8, 65, 512, seed=0)
    state, l0 = step(state, (toks,))
    state, l1 = step(state, (toks,))
    assert np.isfinite(float(l0)) and float(l1) < float(l0)


def test_profile_tool_reports_device_time_on_chip(tmp_path):
    """tpudist.bench.profile end-to-end on the chip: nonzero per-op device
    times, matmuls dominating."""
    import pytest
    pytest.importorskip("xprof")
    import json as _json

    from tpudist.bench import profile as prof
    rc = prof.main([
        "--steps", "2", "--top", "5",
        "--trace-dir", str(tmp_path / "trace"),
        "--out", str(tmp_path / "prof.json"),
        "--model", "transformer", "--train-batch-size", "4",
        "--n-samples", "4", "--seq-len", "256", "--n-layers", "2",
        "--dtype", "bfloat16",
    ])
    assert rc == 0
    s = _json.loads((tmp_path / "prof.json").read_text())
    assert s["total_us_per_step"] > 0
    assert s["by_category_us"].get("convolution fusion", 0) > 0


def test_checkpoint_roundtrip_on_chip(tmp_path):
    """Orbax save/restore with REAL device buffers (the CPU lane only ever
    roundtrips host-backed arrays): params restored bit-exact and the next
    step's loss identical to an uncheckpointed run."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist import data as tdata, engine
    from tpudist.config import (DataConfig, ModelConfig, ParallelConfig,
                                TrainConfig)
    from tpudist.parallel import build_mesh

    cfg = TrainConfig(
        batch_size=8, lr=1e-3, seed=0, dtype="bfloat16",
        data=DataConfig(n_samples=8),
        model=ModelConfig(name="transformer", vocab_size=512, n_layers=2,
                          d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
                          max_seq_len=64),
        parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = tdata.make_synthetic_tokens(8, 65, 512, seed=0)
    state, _ = step(state, (toks,))

    ck = ckpt_lib.Checkpointer(str(tmp_path / "ck"), use_async=False)
    ck.save(state, epoch=1, step_in_epoch=0)
    ck.close()
    restored, epoch, sie = ckpt_lib.restore_latest_full(
        str(tmp_path / "ck"), state)
    assert (epoch, sie) == (1, 0)
    # EVERY leaf — params AND Adam moments AND step (r5 review: a
    # params-only check lets a corrupted opt_state restore pass)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the next UPDATE step agrees — this routes through the restored
    # moments, which a forward-only loss comparison would not
    s1, l_orig = step(state, (toks,))
    s2, l_rest = step(restored, (toks,))
    assert float(l_orig) == float(l_rest)
    _, l1 = step(s1, (toks,))
    _, l2 = step(s2, (toks,))
    assert float(l1) == float(l2)


def test_sweep_all_to_all_single_device_smoke_on_chip():
    """The sweep's non-all_reduce kinds build and execute on the real
    backend (single-device degenerate ring), and the gate correctly
    reports 'not applicable' (ok=None) rather than pass/fail/crash."""
    from tpudist.bench.sweep import gate, run_sweep

    records = run_sweep(("all_to_all", "ppermute"), "data",
                        min_mb=1, max_mb=1, iters=3)
    assert records, "sweep produced no records"
    for r in records:
        assert r["kind"] in ("all_to_all", "ppermute")
        assert np.isfinite(r["bus_gbps"]) and r["bus_gbps"] >= 0
    v = gate(records, 90.0)
    assert v["ok"] is None and v["per_kind"] == {}, v


def test_fused_xent_bf16_multi_supergroup_grad_on_chip():
    """bf16 inputs at t=20000 (10 supergroups -> two outer dE-partial
    chunks): the per-supergroup bf16 rounding of dE partials must stay
    within the unfused bf16 head's own rounding of the same gradient
    (r4 advisor: the large-t coverage ran f32 only, so the bf16
    multi-supergroup path was never compared against the reference).
    Tolerances are scaled for bf16: dE entries are O(1e-4) sums of
    O(1e-7) terms; the reference itself carries bf16 matmul rounding."""
    t, d, v = 20000, 512, 4096
    h, emb, tgt = _data(t, d, v, dtype=jnp.bfloat16)

    def fused(h, e):
        return fused_lm_head_xent(h, e, tgt)

    def ref(h, e):
        return _ref_loss(h, e, tgt)

    lf, (gh_f, ge_f) = jax.value_and_grad(fused, argnums=(0, 1))(h, emb)
    lr, (gh_r, ge_r) = jax.value_and_grad(ref, argnums=(0, 1))(h, emb)
    np.testing.assert_allclose(float(lf), float(lr), rtol=2e-2)
    # relative-to-max error bounds, with non-vacuity guards: the gradient
    # scales here are tiny (max|dh| ~ 3e-6, max|dE| ~ 8e-4 — emb scaled
    # 0.02, loss mean over 20k tokens), so any absolute atol big enough
    # to absorb bf16 noise would also absorb an all-zeros or sign-flipped
    # backward (r5 review: the first cut of this test was vacuous)
    for got, want, name in ((gh_f, gh_r, "dh"), (ge_f, ge_r, "dE")):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        scale = np.abs(want).max()
        assert scale > 0, f"{name}: reference gradient is all zeros"
        err = np.abs(got - want).max() / scale
        assert err < 0.05, f"{name}: max err {err:.4f} of max |ref| {scale}"


def test_golden_bf16_flagship_two_step_losses_on_chip():
    """Committed golden pin for the flagship config's bf16 two-step loss
    trajectory on a real chip (batch 4, seed 0, same synthetic batch both
    steps). The CPU lane cannot see real-MXU bf16 rounding; a kernel or
    engine change that shifts on-chip numerics materially must show up as
    a diff of these constants, reviewed — not drift silently. Golden
    measured on TPU v5 lite, jax 0.9 (r5); rtol covers compiler-
    scheduling noise across libtpu builds, not semantic change."""
    from tpudist import data as tdata, engine
    from tpudist.config import (DataConfig, ParallelConfig, TrainConfig,
                                flagship_model_config)
    from tpudist.parallel import build_mesh

    cfg = TrainConfig(batch_size=4, lr=1e-3, seed=0, dtype="bfloat16",
                      data=DataConfig(n_samples=4),
                      model=flagship_model_config(max_seq_len=512),
                      parallel=ParallelConfig(data=-1))
    mesh = build_mesh(cfg.parallel)
    state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step = engine.make_train_step(cfg, mesh)
    toks = tdata.make_synthetic_tokens(4, 513, cfg.model.vocab_size, seed=0)
    losses = []
    for _ in range(2):
        state, loss = step(state, (toks,))
        losses.append(float(loss))
    GOLDEN = (10.9293, 7.9324)
    np.testing.assert_allclose(losses, GOLDEN, rtol=5e-3)


# ------------------------------------------------------------------ #
# the serve path's decode kernel (ops/pallas/paged_attention.py)      #
# ------------------------------------------------------------------ #

def _serve_cell_case(window, seed=0):
    """The `internlm2` serve cells' own shapes: 16 slots, 256 + 1 pages of
    64, 16 heads over 8 kv heads x 128, a 24-layer bfloat16 pool; rows
    ragged from one token to the whole 1280, one slot unmapped, one with
    a stale entry behind its pages. ``first`` is a slot's first query
    position (its length before the window)."""
    slots, h, kv, hd, layers, pages, pt, maxp = 16, 16, 8, 128, 24, 256, 64, 20
    rng = np.random.default_rng(seed)
    kk, kq = jax.random.split(jax.random.PRNGKey(seed))
    shape = (layers, kv, pages + 1, pt, hd)
    pool_k = jax.random.normal(kk, shape, jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.fold_in(kk, 1), shape,
                               jnp.bfloat16)
    q = jax.random.normal(kq, (slots, window, h, hd), jnp.bfloat16)
    first = [0, 1, 63, 64, 65, 127, 128, 300, 511, 512, 513, 700,
             1280 - window, None, 200, 320]
    free = list(rng.permutation(pages))
    table = np.full((slots, maxp), -1, np.int32)
    pos = np.zeros((slots, window), np.int32)
    for s, n in enumerate(first):
        if n is None:
            pos[s] = 900            # a finished slot: stale length, no row
            continue
        pos[s] = n + np.arange(window)
        for j in range((n + window - 1) // pt + 1):
            table[s, j] = free.pop()
    table[14, 6] = free.pop()       # a stale entry past the slot's pages
    return q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(pos), first


@pytest.mark.parametrize("layer,window,bound", [
    (0, 1, "own"), (23, 1, "own"), (23, 3, "own"), (23, 4, "block")])
def test_paged_attention_kernel_matches_masked_read_on_chip(layer, window,
                                                            bound):
    """Mosaic-compiled, at the cell's shapes, against the XLA masked read
    it replaces on the TPU (bfloat16 tolerance: the kernel keeps its
    scores in float32, the read rounds them to bfloat16). Window 1 is the
    decode step, window 3 a verify forward's, window 4 under the bound
    ``block`` a block-diffusion model's block: every row reads up to the
    window's last position, given to both reads in the positions' place."""
    from tpudist.models import transformer as T
    from tpudist.ops.pallas import paged_attention as pa
    q, pool_k, pool_v, table, pos, first = _serve_cell_case(window)
    if bound == "block":
        pos = jnp.broadcast_to(pos[:, -1:], pos.shape)
    pt = pool_k.shape[3]
    assert T._use_paged_kernel(q.shape, pool_k.shape, pool_k.dtype, pt)

    @jax.jit
    def kernel(q, pk, pv, layer, table, pos):
        return pa.paged_attention(q, pk, pv, layer,
                                  pa.walk(table, pos, pt, pk.shape[2]))

    ref = jax.jit(T._masked_pool_read, static_argnums=(6,))(
        q, pool_k, pool_v, jnp.int32(layer), table, pos, pt)
    out = kernel(q, pool_k, pool_v, jnp.int32(layer), table, pos)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    assert np.isfinite(out).all()
    worst = {s: float(np.abs(out[s] - ref[s]).max())
             for s, n in enumerate(first) if n is not None}
    assert max(worst.values()) < 4e-2, worst
    assert not out[13].any()        # nothing mapped: skipped


def test_paged_attention_kernel_at_the_full_layers_geometry_on_chip():
    """``cohere2moe``'s full layer as the `cmdaplus` cell reads it, Mosaic-
    compiled: 32 slots, 128 query heads over 8 kv heads x 128 (a group of
    16), 64-token pages, rows of 144 entries that map 16 to 144 pages, one
    slot freed; against the XLA masked read. The pool is 400 pages, not the
    cell's 3072 (the masked read scores every slot against every page), so
    rows share pages, none twice in a row."""
    from tpudist.models import transformer as T
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, kv, hd, pages, pt, maxp = 32, 128, 8, 128, 400, 64, 144
    rng = np.random.default_rng(36)
    kk, kq = jax.random.split(jax.random.PRNGKey(36))
    shape = (1, kv, pages + 1, pt, hd)
    pool_k = jax.random.normal(kk, shape, jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.fold_in(kk, 1), shape,
                               jnp.bfloat16)
    q = jax.random.normal(kq, (slots, 1, h, hd), jnp.bfloat16)
    n_pages = [16, 144] + [int(n) for n in rng.integers(16, 145, slots - 2)]
    n_pages[7] = 0                  # freed: its row cleared
    table = np.full((slots, maxp), -1, np.int32)
    pos = np.full((slots, 1), 500, np.int32)
    for s, n in enumerate(n_pages):
        if n:
            table[s, :n] = rng.choice(pages, n, replace=False)
            pos[s] = (n - 1) * pt + int(rng.integers(0, pt))
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    assert T._use_paged_kernel(q.shape, pool_k.shape, pool_k.dtype, pt)

    @jax.jit
    def kernel(q, pk, pv, table, pos):
        return pa.paged_attention(q, pk, pv, 0,
                                  pa.walk(table, pos, pt, pk.shape[2]))

    ref = jax.jit(T._masked_pool_read, static_argnums=(6,))(
        q, pool_k, pool_v, jnp.int32(0), table, pos, pt)
    out = kernel(q, pool_k, pool_v, table, pos)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    assert np.isfinite(out).all()
    worst = {s: float(np.abs(out[s] - ref[s]).max())
             for s, n in enumerate(n_pages) if n}
    assert max(worst.values()) < 4e-2, worst
    assert not out[7].any()         # nothing mapped: skipped


def test_paged_attention_kernel_two_bounds_a_slot_on_chip():
    """The `sdar` cell's first denoising forward, which carries the previous
    block's commit, Mosaic-compiled: 128 slots, 32 query heads over 4 kv
    heads x 128, 64-token pages, a window of 8 rows a slot under TWO bounds:
    the previous block's final 4 rows read up to that block's end, the
    current block's 4 up to their own (4 keys more). Blocks start on a page,
    end one, sit at position 0 (both bounds the block's own) and at the
    cache's last block; one slot freed. Against the XLA masked read. The
    pool is 400 pages, not the cell's 3072 (the masked read scores every
    slot against every page), so rows share pages, none twice in a row."""
    from tpudist.models import transformer as T
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, kv, hd, pages, pt, maxp, b = 128, 32, 4, 128, 400, 64, 48, 4
    rng = np.random.default_rng(38)
    kk, kq = jax.random.split(jax.random.PRNGKey(38))
    shape = (1, kv, pages + 1, pt, hd)
    pool_k = jax.random.normal(kk, shape, jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.fold_in(kk, 1), shape,
                               jnp.bfloat16)
    q = jax.random.normal(kq, (slots, 2 * b, h, hd), jnp.bfloat16)
    starts = [0, 4, 60, 64, 68, maxp * pt - b] + [
        int(n) * b for n in rng.integers(1, maxp * pt // b, slots - 6)]
    starts[9] = None                # freed: its row cleared
    table = np.full((slots, maxp), -1, np.int32)
    see = np.full((slots, 2 * b), 900, np.int32)
    for s, start in enumerate(starts):
        if start is None:
            continue
        n = (start + b - 1) // pt + 1
        table[s, :n] = rng.choice(pages, n, replace=False)
        see[s, :b] = max(start - b, 0) + b - 1
        see[s, b:] = start + b - 1
    table, see = jnp.asarray(table), jnp.asarray(see)
    assert T._use_paged_kernel(q.shape, pool_k.shape, pool_k.dtype, pt)
    # eight rows a slot: the grid runs the slots in groups of 32
    assert pa.slot_groups(slots, 2 * b * h, hd, hd, jnp.bfloat16) == 32

    @jax.jit
    def kernel(q, pk, pv, table, see):
        return pa.paged_attention(q, pk, pv, 0,
                                  pa.walk(table, see, pt, pk.shape[2]))

    ref = jax.jit(T._masked_pool_read, static_argnums=(6,))(
        q, pool_k, pool_v, jnp.int32(0), table, see, pt)
    out = kernel(q, pool_k, pool_v, table, see)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    assert np.isfinite(out).all()
    worst = {s: float(np.abs(out[s] - ref[s]).max())
             for s, start in enumerate(starts) if start is not None}
    assert max(worst.values()) < 4e-2, worst
    assert not out[9].any()         # nothing mapped: skipped
    # the commit rows' own bound is what was compared: up to the current
    # block's end they would read other keys
    one = jax.jit(T._masked_pool_read, static_argnums=(6,))(
        q, pool_k, pool_v, jnp.int32(0), table,
        jnp.broadcast_to(see[:, -1:], see.shape), pt)
    assert np.abs(np.asarray(one, np.float32)[4, :b] - ref[4, :b]).max() \
        > 4e-2


@pytest.mark.parametrize("sub", [0, 7])
def test_latent_paged_attention_kernel_matches_masked_read_on_chip(sub):
    """The kernel's LATENT call, Mosaic-compiled at the ``longcat`` cell's
    widths (192 slots in grid steps of 32, 64 query heads as one group,
    rows of 640 lanes whose first 512 are the values, the scale 192 **
    -0.5) against the XLA masked read of the same one pool. The pool is a
    tenth of the cell's: the masked read scores every slot against every
    page. A whole grid step of slots with nothing mapped, slots of 1 to 6
    pages, one with nothing."""
    from tpudist.models import transformer as T
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, row, vw, subs, pages, pt, maxp = 192, 64, 640, 512, 8, 448, 64, 6
    rng = np.random.default_rng(11)
    pool = jnp.asarray(rng.normal(size=(subs, 1, pages + 1, pt, row)),
                       jnp.bfloat16)
    perm = list(rng.permutation(pages))
    table = np.full((slots, maxp), -1, np.int32)
    pos = np.zeros((slots, 1), np.int32)
    first = [None if 64 <= s < 96 or s == 13 else int(rng.integers(
        0, (1 + s % maxp) * pt)) for s in range(slots)]
    for s, p in enumerate(first):
        if p is not None:
            pos[s] = p
            n = p // pt + 1
            table[s, :n] = [perm.pop() for _ in range(n)]
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    q = jnp.asarray(rng.normal(size=(slots, 1, h, row)) * 0.3, jnp.bfloat16)
    assert T._use_paged_kernel(q.shape, pool.shape, pool.dtype, pt, vw)
    assert pa.slot_groups(slots, h, row, vw, q.dtype) == 32
    scale = 192 ** -0.5

    @jax.jit
    def kernel(q, pool, table, pos):
        return pa.paged_attention(q, pool, None, sub,
                                  pa.walk(table, pos, pt, pool.shape[2]),
                                  scale=scale, v_width=vw)

    ref = jax.jit(lambda q, pool, table, pos: T._masked_pool_read(
        q, pool, None, sub, table, pos, pt, scale=scale, v_width=vw))(
            q, pool, table, pos)
    out = kernel(q, pool, table, pos)
    assert out.shape == (slots, 1, h, vw)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    assert np.isfinite(out).all()
    worst = max(float(np.abs(out[s] - ref[s]).max())
                for s, p in enumerate(first) if p is not None)
    assert worst < 4e-2, worst
    for s, p in enumerate(first):
        if p is None:
            assert not out[s].any()


def test_engine_greedy_tokens_match_masked_read_on_chip(monkeypatch):
    """The whole serve lane over 64 decode steps a request, the kernel's
    tokens against the masked read's (the parent's path), in float32 at
    full matmul precision so that no near-tie decides: token for token."""
    from tpudist.config import ModelConfig, ParallelConfig
    from tpudist.models import transformer as T
    from tpudist.parallel.mesh import build_mesh
    from tpudist.serve import scheduler as sched
    from tpudist.serve.engine import PagedServeEngine, init_params

    cfg = ModelConfig(name="transformer", vocab_size=2048, n_layers=4,
                      d_model=1024, n_heads=8, n_kv_heads=4, d_ff=2048,
                      max_seq_len=512)
    mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    params = init_params(cfg, mesh, seed=0)
    reqs = sched.make_requests(6, prompt_pad=256, vocab_size=cfg.vocab_size,
                               max_new=64, rate=0.0, seed=3, prompt_min=40)
    outs, mosaic = {}, {}
    with jax.default_matmul_precision("highest"):
        for path in ("kernel", "masked_read"):
            if path == "masked_read":
                monkeypatch.setattr(T, "_use_paged_kernel",
                                    lambda *a, **kw: False)
            engine = PagedServeEngine(cfg, mesh, slots=4, max_seq=512,
                                      prompt_pad=256, decode_k=8,
                                      page_tokens=64, dtype=jnp.float32)
            engine.warmup(params)
            summary = sched.run_serve(engine, params, reqs)
            assert summary["completed"] == len(reqs), summary["partition"]
            outs[path] = {rid: r["tokens"]
                          for rid, r in summary["results"].items()}
            jitted, args = engine._programs["decode_k8"][:2]
            mosaic[path] = "tpu_custom_call" in jitted.lower(*args).as_text()
    assert mosaic == {"kernel": True, "masked_read": False}, mosaic
    assert all(len(t) == 64 for t in outs["kernel"].values())
    assert outs["kernel"] == outs["masked_read"]


@pytest.mark.parametrize("n,block,router", [
    (512, 64, "random"), (1024, 128, "random"), (512, 64, "half_real"),
    (512, 64, "one_expert")])
def test_grouped_experts_kernel_matches_the_loop_on_chip(monkeypatch, n,
                                                         block, router):
    """Mosaic-compiled, at the `sdar` cell's shapes (128 experts of 2048 x
    768 as one stack a leaf, top-8; 512 tokens a dispatch in blocks of 64,
    1024 a prefill in blocks of 128), ``dropless.routed`` through the
    grouped kernel against its own loop, the path it leaves (bfloat16
    tolerance: the two differ in the order of the MXU's accumulation and
    of a token's eight rows' sum, and XLA may keep ``silu(g) * u`` wider
    than bfloat16). ``one_expert``: every token's first choice is expert
    5, eight tiles of one block index; ``half_real``: every other token is
    none."""
    from tpudist.models import dropless
    from tpudist.ops.pallas import grouped_experts as ge
    E, d, dff, k = 128, 2048, 768, 8
    ks = jax.random.split(jax.random.PRNGKey(n), 6)
    y = jax.random.normal(ks[0], (n, d), jnp.bfloat16)
    ex = (jax.random.normal(ks[1], (E, d, dff), jnp.bfloat16) / d ** 0.5,
          jax.random.normal(ks[2], (E, d, dff), jnp.bfloat16) / d ** 0.5,
          jax.random.normal(ks[3], (E, dff, d), jnp.bfloat16) / dff ** 0.5)
    logits = jax.random.normal(ks[4], (n, E), jnp.float32)
    if router == "one_expert":
        logits = logits.at[:, 5].set(10.0)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    top_w = top_p / top_p.sum(axis=-1, keepdims=True)
    real = (jnp.arange(n) % 2 == 0) if router == "half_real" else None
    assert dropless.block_rows(n, k, E) == block
    assert dropless.path(ex, n, k, E, jnp.bfloat16) == "grouped"

    def run():
        f = jax.jit(lambda y, e, w, *ex: dropless.routed(
            y, e, w, ex, first=0, held=E, n_routed=E, real=real))
        text = f.lower(y, top_e, top_w, *ex).as_text()
        out, stats = f(y, top_e, top_w, *ex)
        return np.asarray(out), np.asarray(stats), "tpu_custom_call" in text

    got, stats, mosaic = run()
    monkeypatch.setattr(dropless, "_use_grouped_kernel", lambda *a: False)
    want, want_stats, loop_mosaic = run()
    assert mosaic and not loop_mosaic
    np.testing.assert_array_equal(stats, want_stats)
    pairs = n * k // (2 if real is not None else 1)
    assert stats[0] == pairs and stats[2] >= stats[1] > 0
    if router == "one_expert":
        assert stats[2] >= stats[1] + n // block - 1
    assert np.isfinite(got).all()
    scale = float(np.abs(want).max())
    assert float(np.abs(got - want).max()) < 2.0 ** -6 * scale, scale
    if real is not None:
        assert not got[1::2].any()


def test_engine_serves_the_same_tokens_grouped_and_looped_on_chip(
        monkeypatch):
    """The whole serve lane of a block-diffusion expert model, the grouped
    kernel's tokens against the loop's (the parent's path), in float32 at
    full matmul precision so that no near-tie decides: token for token,
    and the engine says which path each run's programs took."""
    from tpudist.config import ModelConfig, ParallelConfig
    from tpudist.models import dropless, sdarmoe
    from tpudist.obs import trace as trace_lib
    from tpudist.parallel.mesh import build_mesh
    from tpudist.serve import scheduler as sched
    from tpudist.serve.engine import PagedServeEngine

    cfg = ModelConfig(name="sdarmoe", vocab_size=512, n_layers=2,
                      d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
                      d_ff=128, n_experts=16, expert_top_k=2,
                      rope_theta=1e6, norm_eps=1e-6, block_length=4,
                      denoise_steps=4, mask_token_id=511)
    mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    params = sdarmoe.init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    reqs = []
    for i, (pl, mn) in enumerate([(40, 33), (17, 64), (64, 21), (9, 40),
                                  (33, 64), (50, 12)]):
        t = np.zeros(64, np.int32)
        t[:pl] = rng.integers(0, 511, (pl,))
        reqs.append(sched.Request(rid=i, arrival_s=0.0, tokens=t,
                                  prompt_len=pl, max_new=mn))
    outs, said, mosaic = {}, {}, {}
    with jax.default_matmul_precision("highest"):
        for path in ("grouped", "loop"):
            if path == "loop":
                monkeypatch.setattr(dropless, "_use_grouped_kernel",
                                    lambda *a: False)
            tracer = trace_lib.configure(enabled=True)
            try:
                engine = PagedServeEngine(cfg, mesh, slots=4, max_seq=256,
                                          prompt_pad=64, page_tokens=64,
                                          dtype=jnp.float32)
                engine.warmup(params)
                summary = sched.run_serve(engine, params, reqs)
                said[path] = [e["args"] for e in tracer.events()
                              if e["name"] == "experts_path"]
            finally:
                trace_lib.configure(enabled=False)
            assert summary["completed"] == len(reqs), summary["partition"]
            assert summary["moe_blocks_mean"] \
                >= summary["moe_experts_hit_mean"] > 0
            outs[path] = {rid: (r["tokens"], r["unmask_step"])
                          for rid, r in summary["results"].items()}
            jitted, args = engine._programs["denoise_b4"][:2]
            mosaic[path] = jitted.lower(*args).as_text().count(
                "grouped_experts")
    assert said == {"grouped": [{"path": "grouped", "prefill": "grouped"}],
                    "loop": [{"path": "loop", "prefill": "loop"}]}, said
    assert mosaic["grouped"] > 0 and mosaic["loop"] == 0, mosaic
    assert [len(outs["grouped"][i][0]) for i in range(6)] \
        == [33, 64, 21, 40, 64, 12]
    assert outs["grouped"] == outs["loop"]


def _kda_inputs(seed, n, T=4096, H=32, K=128):
    """The prefill's KDA operands at the `kimilinear` cell's widths, with
    gates drawn as the model draws them (A in U(1, 16), dt log-uniform in
    [1e-3, 0.1]); padding from ``n`` on, beta = 0 and g = 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-6)
    q = l2(jax.random.normal(ks[0], (1, T, H, K))) / np.sqrt(K)
    k = l2(jax.random.normal(ks[1], (1, T, H, K)))
    v = jax.random.normal(ks[2], (1, T, H, K))
    a = jax.random.uniform(ks[3], (H, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (H, K), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    g = -a * jax.nn.softplus(0.5 * jax.random.normal(ks[5], (1, T, H, K))
                             + jnp.log(jnp.expm1(dt)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[0], (1, T, H)))
    real = (jnp.arange(T) < n)[None, :, None]
    return (q, k, v, jnp.where(real[..., None], g, 0.0),
            jnp.where(real, beta, 0.0))


def _kda_gaps(seed, n):
    """The kernel's largest gaps to ``chunk_kda`` at the real positions
    and in the state, each over the reference's largest magnitude."""
    from tpudist.models import kimilinear as M
    from tpudist.ops.pallas import kda_chunk as kc
    q, k, v, g, beta = _kda_inputs(seed, n)
    b, T, H, K = q.shape
    flat = lambda x: x.reshape(b, T, -1)
    # traced afresh: the kernel's own jit would keep its first trace
    o, S = jax.jit(lambda *x: kc.kda_chunk.__wrapped__(
        *map(flat, x[:4]), x[4], x[5], chunk=M.CHUNK,
        sub=M.SUB))(q, k, v, g, beta, jnp.int32(n))
    o_ref, S_ref = jax.jit(M.chunk_kda)(q, k, v, g, beta)
    o, S, o_ref, S_ref = map(np.asarray, (o.reshape(b, T, H, K), S,
                                          o_ref, S_ref))
    rel = lambda x, y: float(np.abs(x - y).max() / np.abs(y).max())
    assert np.isfinite(o).all() and not o[:, n:].any()
    return rel(o[:, :n], o_ref[:, :n]), rel(S, S_ref)


def test_kda_chunk_kernel_matches_chunk_kda_on_chip(monkeypatch):
    """One prompt of 2,300 tokens padded to 4,096 at the cell's widths,
    through the Mosaic-compiled kernel and through ``chunk_kda``: both
    float32 at HIGHEST, so the gap is rounding's. The control, the
    kernel's products at DEFAULT (one bfloat16 pass), misses the same
    bound: a lost HIGHEST would show."""
    from tpudist.ops.pallas import kda_chunk as kc
    o_gap, s_gap = _kda_gaps(4100000411, 2300)
    assert o_gap <= 1e-4 and s_gap <= 1e-4
    monkeypatch.setattr(kc, "_HI", jax.lax.Precision.DEFAULT)
    o_gap, s_gap = _kda_gaps(4100000411, 2300)
    assert max(o_gap, s_gap) > 1e-4
