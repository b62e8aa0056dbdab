"""The prefill's chunked KDA as one Pallas kernel
(``tpudist/ops/pallas/kda_chunk.py``), under the Pallas interpreter at tiny
widths: against ``kimilinear.chunk_kda`` (the CPU's path and the kernel's
reference) and against the decode's token-by-token ``kda_step``, on q, k,
v, g and beta made by a tiny model's own KDA projections; what
``supports`` takes; and which path ``_kda_prefill`` takes off the TPU.

The tolerance is the chunked prefill's own against the recurrence
(``test_kimilinear.py``): the same float32 mathematics in another order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.config import ModelConfig
from tpudist.models import kimilinear as M
from tpudist.models import transformer as T
from tpudist.ops.pallas import kda_chunk as kc

jax.config.update("jax_default_matmul_precision", "highest")

TOL = 2e-5
H, K = 2, 8


def config():
    return ModelConfig(
        name="kimilinear", vocab_size=64, n_layers=2, d_model=32,
        n_heads=4, d_ff=16, d_ff_dense=48, n_experts=16,
        n_experts_held=4, expert_first=0, expert_top_k=4,
        n_shared_experts=1, routed_scaling=2.446, norm_eps=1e-5,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, kda_heads=H, kda_head_dim=K, kda_conv=4,
        first_dense=1, full_attn_every=4)


def sublayer(decay=1.0):
    """A KDA layer's float32 weights; ``decay`` scales ``dt_bias`` up as
    ``test_the_chunked_prefill_is_the_recurrence`` does: at 30, decays of
    ~e^-480 a token, whose products across a chunk would overflow any
    factored form not taken through a reference point."""
    cfg = config()
    params = M.init(jax.random.PRNGKey(5), cfg)
    sp = jax.tree.map(lambda a: a.astype(jnp.float32),
                      dict(params["layers"][1]["mix"]))
    sp["dt_bias"] = sp["dt_bias"] * decay + (decay > 1) * 30.0
    return cfg, sp


def inputs(n, pad, decay):
    """q (scaled), k, v, g, beta ``(1, pad, ...)`` from the layer's own
    projections of a random residual stream, padding as the prefill makes
    it (beta = 0 and g = 0 from ``n`` on)."""
    cfg, sp = sublayer(decay)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, pad, 32), jnp.float32)
    u = T.rmsnorm(x, sp["in_norm"], cfg.norm_eps)
    q, k, v, g, beta, _ = M._kda_in(u, sp, cfg,
                                    jnp.zeros((1, 3, 3 * H * K)))
    real = (jnp.arange(pad) < n)[None, :, None]
    return q, k, v, jnp.where(real[..., None], g, 0.0), \
        jnp.where(real, beta, 0.0)


def kernel(q, k, v, g, beta, n):
    b, pad = q.shape[:2]
    flat = lambda a: a.reshape(b, pad, -1)
    o, S = kc.kda_chunk(*map(flat, (q, k, v, g)), beta, jnp.int32(n),
                        chunk=M.CHUNK, sub=M.SUB, interpret=True)
    return o.reshape(v.shape), S


@pytest.mark.parametrize("n,pad,decay", [
    (37, 64, 1.0), (64, 128, 1.0), (100, 192, 1.0), (130, 192, 30.0),
    (128, 128, 1.0), (1, 64, 1.0)],
    ids=["part", "whole-then-pad", "two-and-a-part", "decay30",
         "on-the-edge", "one-token"])
def test_the_kernel_is_the_chunked_form_and_the_recurrence(n, pad, decay):
    """The chunked prefill's cases (none whole, one whole, a part, two and
    a part at ~e^-480 a token), a prompt that ends on a chunk's edge and
    fills the pad, one token. At the real positions and in the final state
    the kernel is ``chunk_kda`` and the recurrence; past ``prompt_len`` it
    is exactly 0."""
    q, k, v, g, beta = inputs(n, pad, decay)
    o, S = kernel(q, k, v, g, beta, n)
    o_ref, S_ref = M.chunk_kda(q, k, v, g, beta)
    np.testing.assert_allclose(o[:, :n], o_ref[:, :n], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(S, S_ref, atol=TOL, rtol=TOL)
    # the decode's step, a token at a time from a zero state
    S1, live, outs = jnp.zeros((1, H, K, K)), jnp.ones((1,), bool), []
    for t in range(n):
        S1, o1 = M.kda_step(S1, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t], live)
        outs.append(o1)
    np.testing.assert_allclose(o[:, :n], jnp.stack(outs, axis=1), atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(S, S1, atol=TOL, rtol=TOL)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_array_equal(o[:, n:], 0.0)


@pytest.mark.parametrize("k_shape,v_shape,ok", [
    ((1, 4096, 32, 128), (1, 4096, 32, 128), True),
    ((1, 64, 30, 128), (1, 64, 30, 128), True),
    ((2, 512, 4, 256), (2, 512, 4, 128), True),
    ((1, 4096, 32, 64), (1, 4096, 32, 128), False),
    ((1, 4096, 32, 128), (1, 4096, 32, 96), False),
    ((1, 4000, 32, 128), (1, 4000, 32, 128), False),
    ((1, 4096, 32, 8), (1, 4096, 32, 8), False),
    ((1, 4096, 32, 4096), (1, 4096, 32, 4096), False)],
    ids=["cell", "one-chunk", "wide-k", "half-lanes-k", "odd-v",
         "pad-off-chunk", "tiny-widths", "over-vmem"])
def test_supports_by_shape(k_shape, v_shape, ok):
    """Lanes full, the pad whole chunks, and fast memory enough; anything
    else stays on ``chunk_kda``."""
    assert kc.supports(k_shape, v_shape, chunk=M.CHUNK) is ok


def test_off_the_tpu_the_prefill_takes_chunk_kda(monkeypatch):
    """On the CPU the prefill's KDA is ``chunk_kda`` even at shapes the
    kernel supports: the kernel is never called."""
    assert M._use_chunk_kernel((1, 4096, 32, 128), (1, 4096, 32, 128)) \
        is False

    def refuse(*a, **kw):
        raise AssertionError("the kernel was called off the TPU")
    monkeypatch.setattr(kc, "kda_chunk", refuse)
    cfg, sp = sublayer()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32), jnp.float32)
    y, S, _ = M._kda_prefill(x, sp, cfg, jnp.int32(40))
    assert bool(jnp.isfinite(y).all()) and S.shape == (1, H, K, K)
