"""Ring attention vs dense attention: numerical agreement under sequence
sharding (long-context extension; no reference counterpart — SURVEY.md §5.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.config import ParallelConfig
from tpudist.models.transformer import _attention
from tpudist.ops.ring_attention import make_ring_attention
from tpudist.parallel import build_mesh


@pytest.fixture(scope="module")
def ctx_mesh(devices8):
    return build_mesh(ParallelConfig(data=1, context=8), devices=devices8)


def _qkv(key, b=2, s=64, h=4, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, h, d)),
            jax.random.normal(kk, (b, s, h, d)),
            jax.random.normal(kv, (b, s, h, d)))


def test_ring_matches_dense_causal(ctx_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ring = make_ring_attention(ctx_mesh, "context", causal=True)
    out_ring = np.asarray(ring(q, k, v))
    out_dense = np.asarray(_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out_ring, out_dense, rtol=2e-5, atol=2e-5)


def test_ring_matches_dense_non_causal(ctx_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(1))
    ring = make_ring_attention(ctx_mesh, "context", causal=False)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                               np.asarray(_attention(q, k, v, causal=False)),
                               rtol=2e-5, atol=2e-5)


def test_ring_grads_match_dense(ctx_mesh):
    """Backward through the ring (ppermute transposes to reverse ring) must
    match dense attention gradients — training correctness."""
    q, k, v = _qkv(jax.random.PRNGKey(2), b=1, s=32, h=2, d=8)
    ring = make_ring_attention(ctx_mesh, "context", causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_ring_gqa_compact_kv_matches_dense(ctx_mesh):
    """Grouped-query attention: compact kv blocks (2 kv heads, 4 q heads)
    travel the ring and expand inside the kernel; must match dense GQA."""
    key = jax.random.PRNGKey(7)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, 64, 4, 16))
    k = jax.random.normal(kk, (2, 64, 2, 16))
    v = jax.random.normal(kv_, (2, 64, 2, 16))
    ring = make_ring_attention(ctx_mesh, "context", causal=True)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                               np.asarray(_attention(q, k, v, causal=True)),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_inputs(ctx_mesh):
    q, k, v = _qkv(jax.random.PRNGKey(3))
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ring = make_ring_attention(ctx_mesh, "context", causal=True)
    out = ring(q, k, v)
    assert out.dtype == jnp.bfloat16
    dense = _attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_zigzag_permute_roundtrip():
    from tpudist.ops.ring_attention import zigzag_inverse, zigzag_permute
    x = jnp.arange(2 * 32 * 3).reshape(2, 32, 3)
    for n in (2, 4, 8):
        y = zigzag_permute(x, n)
        np.testing.assert_array_equal(np.asarray(zigzag_inverse(y, n)),
                                      np.asarray(x))
    with pytest.raises(ValueError, match="divisible"):
        zigzag_permute(x[:, :30], 8)


def test_zigzag_halves_causal_attention_flops(ctx_mesh):
    """The point of the zigzag layout (VERDICT r1 weak #3): under causal
    masking the consume-every-block ring pays the full S×S score/value
    matmuls on every device; zigzag computes only live chunk pairs —
    compiled FLOPs must drop to ~half (plus GQA-independent overheads)."""
    q, k, v = _qkv(jax.random.PRNGKey(0), s=512)

    def flops_of(layout):
        from jax.sharding import NamedSharding, PartitionSpec as P
        import functools
        from tpudist.ops.ring_attention import ring_attention_local
        spec = P(None, "context", None, None)

        @functools.partial(jax.shard_map, mesh=ctx_mesh,
                           in_specs=(spec, spec, spec), out_specs=spec,
                           check_vma=False)
        def f(q, k, v):
            # unroll so cost_analysis counts every hop (a fori_loop body
            # is otherwise counted once regardless of trip count)
            return ring_attention_local(q, k, v, "context", causal=True,
                                        layout=layout, unroll=True)
        sh = NamedSharding(ctx_mesh, spec)
        args = [jax.device_put(x, sh) for x in (q, k, v)]
        cost = jax.jit(f).lower(*args).compile().cost_analysis()
        return cost.get("flops")

    dense_fl = flops_of("contig")
    zig_fl = flops_of("zigzag")
    if not dense_fl or not zig_fl:
        pytest.skip("backend reports no flops in cost_analysis")
    # ideal ratio at n=8: (2n+1)/4n = 0.53; allow overhead slack
    assert zig_fl < 0.65 * dense_fl, (zig_fl, dense_fl)


def test_ring_flash_hops_match_einsum_causal(ctx_mesh):
    """Flash-kernel hops (pallas interpreter on CPU) vs the einsum
    reference schedule: same zigzag ring, kernel-eligible chunk shapes
    (c = 2048/8/2 = 128, head_dim 128), GQA compact kv on the ring."""
    key = jax.random.PRNGKey(11)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2048, 2, 128))
    k = jax.random.normal(kk, (1, 2048, 1, 128))
    v = jax.random.normal(kv_, (1, 2048, 1, 128))
    flash = make_ring_attention(ctx_mesh, "context", causal=True,
                                use_flash=True)
    einsum = make_ring_attention(ctx_mesh, "context", causal=True,
                                 use_flash=False)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(einsum(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_hops_grads_match_einsum(ctx_mesh):
    """Backward through the lse merge: each hop's kernel receives an
    (do, dlse) cotangent pair that must reproduce the einsum ring's
    gradients — the differentiable-lse contract."""
    key = jax.random.PRNGKey(12)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2048, 1, 128))
    k = jax.random.normal(kk, (1, 2048, 1, 128))
    v = jax.random.normal(kv_, (1, 2048, 1, 128))
    flash = make_ring_attention(ctx_mesh, "context", causal=True,
                                use_flash=True)
    einsum = make_ring_attention(ctx_mesh, "context", causal=True,
                                 use_flash=False)

    def loss(ring):
        return lambda q, k, v: jnp.sum(ring(q, k, v) ** 2)

    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss(einsum), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_flash_hops_non_causal(ctx_mesh):
    """Contig non-causal ring through the kernel (whole-shard unmasked
    hops merged by lse) vs the einsum reference."""
    key = jax.random.PRNGKey(13)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 1024, 1, 128))
    k = jax.random.normal(kk, (1, 1024, 1, 128))
    v = jax.random.normal(kv_, (1, 1024, 1, 128))
    flash = make_ring_attention(ctx_mesh, "context", causal=False,
                                use_flash=True)
    einsum = make_ring_attention(ctx_mesh, "context", causal=False,
                                 use_flash=False)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(einsum(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_shape_gate(ctx_mesh, monkeypatch):
    """use_flash=True with kernel-ineligible shapes must raise loudly
    (head_dim 16 < 128), and the auto path must fall back silently —
    through the SHAPE gate, not the backend gate (the interpret env var
    takes the backend guard out of the way)."""
    from tpudist.ops.ring_attention import flash_hops_supported
    q, k, v = _qkv(jax.random.PRNGKey(14))      # s=64, d=16: ineligible
    assert not flash_hops_supported(q.shape, k.shape)
    ring = make_ring_attention(ctx_mesh, "context", causal=True,
                               use_flash=True)
    with pytest.raises(ValueError, match="flash_hops_supported"):
        ring(q, k, v)
    # auto (None) must reach the shape check (backend guard disarmed) and
    # still route to einsum for these shapes
    monkeypatch.setenv("TPUDIST_RING_FLASH_INTERPRET", "1")
    auto = make_ring_attention(ctx_mesh, "context", causal=True)
    np.testing.assert_allclose(np.asarray(auto(q, k, v)),
                               np.asarray(_attention(q, k, v, causal=True)),
                               rtol=2e-5, atol=2e-5)


def test_zigzag_degenerate_single_device_ring(devices8):
    """Regression (r2 review): a context axis of size 1 must reduce to
    plain local causal attention — the zigzag schedule's peeled final hop
    would otherwise re-consume the local block."""
    mesh1 = build_mesh(ParallelConfig(data=8, context=1), devices=devices8)
    q, k, v = _qkv(jax.random.PRNGKey(3), s=32)
    ring = make_ring_attention(mesh1, "context", causal=True)
    want = np.asarray(_attention(q, k, v, causal=True))
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), want,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,s", [(True, 256), (False, 256),
                                      (True, 128)])
def test_flash_degenerate_single_device_ring(devices8, causal, s):
    """use_flash on a size-1 context axis must run exactly one local
    kernel call (r4 review: the contig-flash init+peel pair would consume
    the local block twice; correct only by merge idempotence and 2× the
    compute) and match the einsum path. s=128 is hop-INELIGIBLE (half
    chunks of 64) but whole-shard eligible — the gate must accept it on a
    degenerate ring (r4 review)."""
    mesh1 = build_mesh(ParallelConfig(data=8, context=1), devices=devices8)
    key = jax.random.PRNGKey(15)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, s, 2, 128))
    k = jax.random.normal(kk, (1, s, 1, 128))
    v = jax.random.normal(kv_, (1, s, 1, 128))
    flash = make_ring_attention(mesh1, "context", causal=causal,
                                use_flash=True)
    einsum = make_ring_attention(mesh1, "context", causal=causal,
                                 use_flash=False)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(einsum(q, k, v)),
                               rtol=2e-5, atol=2e-5)
