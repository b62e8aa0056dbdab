"""Pallas flash attention vs the dense reference (forward + gradients),
run through the pallas interpreter on CPU. Shapes honor the kernel's TPU
alignment floor (head_dim and seq multiples of 128) but stay small; block
sizes of 128 force multi-block grids so the online softmax, causal block
skipping, and both backward kernels' accumulators are all exercised."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops.pallas import flash_attention as fa


def _dense_ref(q, k, v, causal=True):
    """Delegates to the ONE shared reference (tpudist.ops.reference) with
    an f32 upcast — this lane's convention is the strictest reference
    (scores and PV in f32 regardless of input dtype)."""
    from tpudist.ops.reference import dense_attention
    out = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=causal)
    return out.astype(q.dtype)


def _data(b=1, s=256, h=2, kv=None, hd=128, seed=0, dtype=jnp.float32):
    kv = kv or h
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _data()
    got = fa.flash_attention(q, k, v, causal=causal, block_q=128,
                             block_k=128, interpret=True)
    want = _dense_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_uneven_blocks():
    # seq 384 with block 256 → falls back to 128-wide blocks via _pick_block
    q, k, v = _data(s=384)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    want = _dense_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_dense(causal):
    q, k, v = _data()
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def f_flash(q, k, v):
        return jnp.vdot(fa.flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            interpret=True), ct)

    def f_dense(q, k, v):
        return jnp.vdot(_dense_ref(q, k, v, causal=causal), ct)

    got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "q k v".split()):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_gqa_grouped_heads():
    q, k, v = _data(h=4, kv=2)
    got = fa.flash_attention(q, k, v, block_q=128, block_k=128,
                             interpret=True)
    want = _dense_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    # dk/dv must group-sum over the repeated query heads
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    got_g = jax.grad(lambda a, b, c: jnp.vdot(fa.flash_attention(
        a, b, c, block_q=128, block_k=128, interpret=True), ct),
        argnums=(1, 2))(q, k, v)
    want_g = jax.grad(lambda a, b, c: jnp.vdot(
        _dense_ref(a, b, c), ct), argnums=(1, 2))(q, k, v)
    for g, w in zip(got_g, want_g):
        assert g.shape == (1, 256, 2, 128)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-3)


def test_bf16():
    q, k, v = _data(dtype=jnp.bfloat16)
    got = fa.flash_attention(q, k, v, block_q=128, block_k=128,
                             interpret=True)
    want = _dense_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-2, rtol=2e-2)
    assert got.dtype == jnp.bfloat16


def test_supports_gates_shapes():
    ok = ((1, 256, 2, 128), (1, 256, 2, 128))
    assert fa.supports(*ok)
    assert not fa.supports((1, 200, 2, 128), ok[1])      # seq not /128
    assert not fa.supports((1, 256, 2, 64), ok[1])       # head_dim 64
    assert not fa.supports((1, 256, 3, 128), ok[1])      # heads not /kv


def test_fused_rope_matches_rotate_then_attend():
    from tpudist.models.transformer import apply_rope, precompute_rope
    q, k, v = _data()
    cos, sin = precompute_rope(q.shape[1], q.shape[-1])
    got = fa.flash_attention(q, k, v, cos=cos, sin=sin, block_q=128,
                             block_k=128, interpret=True)
    want = _dense_ref(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)

    # gradients flow through the in-kernel rotation and counter-rotation
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    got_g = jax.grad(lambda a, b, c: jnp.vdot(fa.flash_attention(
        a, b, c, cos=cos, sin=sin, block_q=128, block_k=128,
        interpret=True), ct), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(lambda a, b, c: jnp.vdot(_dense_ref(
        apply_rope(a, cos, sin), apply_rope(b, cos, sin), c), ct),
        argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got_g, want_g, "q k v".split()):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def _ref_lse(q, k, v, causal):
    """Reference per-row log-sum-exp of the scaled (masked) scores."""
    hd = q.shape[-1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        sc = jnp.where(mask, sc, -1e30)
    return jax.nn.logsumexp(sc, axis=-1)          # (b, h, s)


@pytest.mark.parametrize("causal", [True, False])
def test_with_lse_forward(causal):
    q, k, v = _data()
    o, lse = fa.flash_attention_with_lse(q, k, v, causal=causal,
                                         block_q=128, block_k=128,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(_dense_ref(q, k, v, causal)),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_ref_lse(q, k, v, causal)),
                               atol=2e-5, rtol=1e-5)


def test_with_lse_gradients_include_dlse():
    """A loss consuming BOTH outputs: the lse cotangent must flow (it
    folds into the backward's delta constant) — checked against autodiff
    of the dense reference computing the same pair."""
    q, k, v = _data(s=256)
    kc = jax.random.split(jax.random.PRNGKey(7), 2)
    ct_o = jax.random.normal(kc[0], q.shape)
    ct_l = jax.random.normal(kc[1], (q.shape[0], q.shape[2], q.shape[1]))

    def loss_kernel(q, k, v):
        o, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                             block_q=128, block_k=128,
                                             interpret=True)
        return jnp.vdot(o, ct_o) + jnp.vdot(lse, ct_l)

    def loss_ref(q, k, v):
        return (jnp.vdot(_dense_ref(q, k, v, True), ct_o)
                + jnp.vdot(_ref_lse(q, k, v, True), ct_l))

    g_got = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_partial_merge_matches_full_attention():
    """The ring building block: attend to two kv halves separately
    (non-causal), merge the (o, lse) partials with the logsumexp rule, and
    the merged result — AND its gradients through both kernel calls —
    must match single-call full attention."""
    q, k, v = _data(s=256)
    k1, k2 = k[:, :128], k[:, 128:]
    v1, v2 = v[:, :128], v[:, 128:]

    def merged(q, k1, v1, k2, v2):
        o1, l1 = fa.flash_attention_with_lse(q, k1, v1, causal=False,
                                             block_q=128, block_k=128,
                                             interpret=True)
        o2, l2 = fa.flash_attention_with_lse(q, k2, v2, causal=False,
                                             block_q=128, block_k=128,
                                             interpret=True)
        lse = jnp.logaddexp(l1, l2)                       # (b, h, s)
        w1 = jnp.exp(l1 - lse).transpose(0, 2, 1)[..., None]
        w2 = jnp.exp(l2 - lse).transpose(0, 2, 1)[..., None]
        return o1 * w1 + o2 * w2

    got = merged(q, k1, v1, k2, v2)
    want = fa.flash_attention(q, k, v, causal=False, block_q=128,
                              block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)

    ct = jax.random.normal(jax.random.PRNGKey(11), q.shape)
    g_got = jax.grad(lambda q, k, v: jnp.vdot(merged(
        q, k[:, :128], v[:, :128], k[:, 128:], v[:, 128:]), ct),
        argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda q, k, v: jnp.vdot(fa.flash_attention(
        q, k, v, causal=False, block_q=128, block_k=128, interpret=True),
        ct), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("parallel", [dict(data=4),
                                      dict(data=1, fsdp=2, tensor=2)])
def test_flash_runs_per_shard_where_the_program_is_partitioned(
        devices8, monkeypatch, parallel):
    """GSPMD cannot partition a Mosaic call, which only a multi-chip TPU
    run shows (PR 21: eval, jit + shardings training and serve prefill
    died on it). With the kernel forced on (interpreted) the engine's
    programs — the DP shard_map step, the jit + shardings step, and eval,
    which is auto-partitioned on every mesh — must run it per shard and
    reproduce the one-device XLA-attention trajectory."""
    from tpudist import data, engine
    from tpudist.config import ModelConfig, ParallelConfig, TrainConfig
    from tpudist.models import transformer as T
    from tpudist.parallel import build_mesh

    model = ModelConfig(name="transformer", vocab_size=256, n_layers=1,
                        d_model=256, n_heads=2, n_kv_heads=2, d_ff=256,
                        max_seq_len=128)
    toks = np.asarray(data.make_synthetic_tokens(4, 129, 256, seed=0))
    seen = []

    def run(par, devs):
        cfg = TrainConfig(batch_size=4, model=model, parallel=par,
                          dtype="float32")
        mesh = build_mesh(par, devices=devs)
        state = engine.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step = engine.make_train_step(cfg, mesh)
        state, loss = step(state, (toks,))
        return float(loss), float(engine.make_eval_fn(cfg, mesh)(
            state, (toks,)))

    want = run(ParallelConfig(), devices8[:1])      # XLA attention

    def use_flash(q_shape, k_shape, causal=True):
        seen.append(q_shape)
        return fa.supports(q_shape, k_shape, causal=causal)
    monkeypatch.setattr(T, "_use_flash", use_flash)
    got = run(ParallelConfig(**parallel), devices8[:4])
    assert seen, "the flash path was never taken"
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ------------------------------------------------------------- window


def _band_ref(q, k, v, window):
    """Dense attention under the band mask i - window < j <= i, float32."""
    from tpudist.ops.gqa import expand_gqa
    k, v = expand_gqa(q, k, v)
    s = q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sc = jnp.where((j <= i) & (j > i - window), sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)


# block-aligned (one and two blocks), unaligned either side of a block
# edge, the query alone, wider than the sequence (plain causal)
WINDOWS = [128, 256, 100, 129, 1, 4096]


@pytest.mark.parametrize("window", WINDOWS)
def test_window_forward_matches_a_dense_band(window):
    q, k, v = _data(s=512, h=4, kv=2, seed=3)
    got = fa.flash_attention(q, k, v, window=window, block_q=128,
                             block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_band_ref(q, k, v, window)),
                               atol=2e-5, rtol=1e-4)


def test_window_forward_with_unequal_blocks_and_one_kv_block():
    """block_q != block_k moves which kv blocks a row block needs; one kv
    block takes the kernel's single-pass branch."""
    q, k, v = _data(s=512, seed=4)
    for bq, bk in ((256, 128), (128, 256), (128, 512)):
        got = fa.flash_attention(q, k, v, window=200, block_q=bq,
                                 block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_band_ref(q, k, v, 200)),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window", [128, 100, 300])
def test_window_gradients_match_a_dense_band(window):
    """The backward kernels take the band too (a gradient that stayed
    causal under a windowed forward would be a silent fault)."""
    q, k, v = _data(s=384, h=4, kv=2, seed=5)
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g_flash = jax.grad(lambda q, k, v: jnp.vdot(fa.flash_attention(
        q, k, v, window=window, block_q=128, block_k=128,
        interpret=True), ct), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.vdot(
        _band_ref(q, k, v, window), ct), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=1e-4)


def test_window_is_refused_where_it_means_nothing():
    q, k, v = _data()
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=False, window=64,
                           interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=0, interpret=True)


def _block_ref(q, k, v, block):
    """Dense attention under the block-causal mask j // block <= i //
    block, float32."""
    from tpudist.ops.gqa import expand_gqa
    k, v = expand_gqa(q, k, v)
    s = q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sc = jnp.where(j // block <= i // block, sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)


# a block-diffusion model's 4, the smallest, a whole q block; beside the
# windows above: the same kernel, another mask parameter
@pytest.mark.parametrize("block,bq,bk", [(4, 128, 128), (2, 128, 128),
                                         (128, 128, 128), (4, 256, 128),
                                         (4, 128, 512)])
def test_block_forward_matches_a_dense_block_mask(block, bq, bk):
    q, k, v = _data(s=512, h=4, kv=2, seed=5)
    got = fa.flash_attention(q, k, v, block=block, block_q=bq, block_k=bk,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_block_ref(q, k, v, block)),
                               atol=2e-5, rtol=1e-4)
    causal = fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                interpret=True)
    # a block's first row sees the keys ahead of it; its last row is causal
    assert np.abs(np.asarray(got[:, 0]) - np.asarray(causal[:, 0])).max() \
        > 1e-3
    np.testing.assert_allclose(np.asarray(got[:, block - 1]),
                               np.asarray(causal[:, block - 1]), atol=2e-5)


def test_block_is_refused_where_it_is_not_built():
    q, k, v = _data()
    for kw in ({"causal": False}, {"window": 64}, {"block": 6},
               {"block": 256}):
        with pytest.raises(ValueError, match="block"):
            fa.flash_attention(q, k, v, interpret=True,
                               **{"block": 4, **kw})
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, block=4, interpret=True).sum())(q)


def test_the_dense_path_takes_the_block_mask_off_the_tpu():
    from tpudist.models import transformer as T
    q, k, v = _data(s=128, h=4, kv=2, seed=6)
    np.testing.assert_allclose(
        np.asarray(T._attention(q, k, v, block=4)),
        np.asarray(_block_ref(q, k, v, 4)), atol=2e-5, rtol=1e-4)


def test_an_unset_window_leaves_the_causal_kernels_as_they_were():
    """The train cell's program must not move: with no window nothing of
    the band is traced (no extra compare in the mask, the kv index map
    the plain one), forward and backward."""
    q, k, v = _data()

    def lowered(**kw):
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, block_q=128, block_k=128,
                                      interpret=True, **kw).sum()
        return jax.jit(jax.grad(loss)).lower(q, k, v).as_text()
    assert lowered() == lowered(window=None) == lowered(block=0)
    assert lowered(window=128) != lowered()
