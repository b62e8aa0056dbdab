"""The grouped expert kernel (``ops/pallas/grouped_experts.py``) and the
path of ``models/dropless.routed`` that calls it, under the Pallas TPU
interpreter at small shapes that honour the kernel's tiling (lanes of 128,
blocks of whole sublane tiles), held to the loop it stands in for on one
TPU chip (the CPU path) AND to the dense sum over the held experts. The
interpreter hands the kernel NaN for memory it never wrote, so "no pair
reads a row of an idle tile" is checked, not assumed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpudist.models import dropless
from tpudist.ops.pallas import grouped_experts as ge

D, DFF = 128, 128


@pytest.fixture()
def grouped(monkeypatch):
    """``dropless.routed`` takes the kernel's path, the kernel runs in
    the interpreter."""
    monkeypatch.setattr(dropless, "_use_grouped_kernel", lambda *a: True)
    monkeypatch.setattr(ge, "grouped_experts", functools.partial(
        ge.grouped_experts, interpret=pltpu.InterpretParams()))


def _weights(held, dtype, seed=0, stacked=True):
    rng = np.random.default_rng(seed)
    ex = tuple(jnp.asarray(rng.normal(size=(held,) + s) / 8, dtype)
               for s in ((D, DFF), (D, DFF), (DFF, D)))
    return ex if stacked else tuple(tuple(w) for w in ex)


def _dense(y, top_e, top_w, experts, first, held, real=None):
    """The plain sum over the held experts, in float32."""
    y32 = y.astype(jnp.float32)
    out = jnp.zeros(y.shape, jnp.float32)
    for i in range(held):
        w = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), axis=-1)
        if real is not None:
            w = jnp.where(real, w, 0.0)
        g, u, dn = (experts[j][i].astype(jnp.float32) for j in range(3))
        out = out + ((jax.nn.silu(y32 @ g) * (y32 @ u)) @ dn) * w[:, None]
    return out


def _routing(case, n, k, routed, rng):
    """(top_e, real) of a case; weights are drawn beside."""
    real = None
    if case == "even":
        # token t takes experts t, t + 1, ...: every expert the same load
        top_e = (np.arange(n)[:, None] + np.arange(k)[None]) % routed
    elif case == "one_expert":
        # every pair of every token on expert 3: many tiles of one block
        # index (k = 1 keeps a token's choices distinct)
        top_e = np.full((n, k), 3)
    elif case == "skewed":
        # the first choice always expert 2, the second anywhere else
        top_e = np.stack([np.full((n,), 2)] + [
            rng.integers(3, routed, (n,)) for _ in range(k - 1)], axis=1)
    elif case == "some_get_nothing":
        top_e = np.stack([rng.permutation(routed // 2)[:k] * 2
                          for _ in range(n)])      # even experts only
    else:
        top_e = np.stack([rng.permutation(routed)[:k] for _ in range(n)])
        if case == "half_masked":
            real = jnp.asarray(np.arange(n) % 2 == 0)
        elif case == "nearly_all_masked":
            # 3 real tokens: nearly every tile of the call is idle
            real = jnp.asarray(np.arange(n) < 3)
        elif case == "nothing_real":
            real = jnp.zeros((n,), bool)
    return jnp.asarray(top_e, jnp.int32), real


# (id, tokens, k, experts routed, first held, held, dtype)
CASES = [
    ("even", 64, 2, 8, 0, 8, jnp.float32),
    ("one_expert", 80, 1, 8, 0, 8, jnp.float32),
    ("skewed", 48, 2, 8, 0, 8, jnp.float32),
    ("some_get_nothing", 48, 2, 8, 0, 8, jnp.float32),
    ("random", 48, 2, 8, 0, 8, jnp.float32),
    ("half_masked", 48, 2, 8, 0, 8, jnp.float32),
    ("nearly_all_masked", 48, 2, 8, 0, 8, jnp.float32),
    ("nothing_real", 32, 2, 8, 0, 8, jnp.float32),
    # a chip's share of the experts: pairs of experts held elsewhere add
    # nothing and point at no row
    ("random", 48, 2, 16, 4, 8, jnp.float32),
    ("half_masked", 48, 2, 16, 8, 8, jnp.float32),
    ("random", 64, 4, 8, 0, 8, jnp.bfloat16),
    ("skewed", 64, 2, 8, 0, 8, jnp.bfloat16),
]


@pytest.mark.parametrize("case,n,k,routed,first,held,dtype", CASES, ids=[
    f"{c[0]}-n{c[1]}k{c[2]}-{c[5]}of{c[3]}-{jnp.dtype(c[6]).name}"
    for c in CASES])
def test_grouped_path_matches_the_loop_and_the_dense_sum(
        grouped, case, n, k, routed, first, held, dtype):
    rng = np.random.default_rng(len(case) + n)
    y = jnp.asarray(rng.normal(size=(n, D)), dtype)
    ex = _weights(held, dtype)
    top_e, real = _routing(case, n, k, routed, rng)
    top_w = jnp.asarray(rng.uniform(0.2, 0.8, (n, k)), jnp.float32)
    kw = dict(first=first, held=held, n_routed=routed, real=real)
    got, stats = jax.jit(lambda y, e, w: dropless.routed(
        y, e, w, ex, **kw))(y, top_e, top_w)
    assert got.shape == (n, D) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dropless, "_use_grouped_kernel", lambda *a: False)
        want, want_stats = jax.jit(lambda y, e, w: dropless.routed(
            y, e, w, ex, **kw))(y, top_e, top_w)
    # one routing, one count of blocks on both paths
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    dense = _dense(y, top_e, top_w, ex, first, held, real)
    # bfloat16: a few roundings of 2**-8 each, against the sum's size
    tol = 1e-5 if dtype == jnp.float32 \
        else 2.0 ** -6 * float(jnp.abs(dense).max())
    assert float(jnp.abs(got - want).max()) < tol
    assert float(jnp.abs(got - dense).max()) < tol
    # what the counts say of the case
    block = dropless.block_rows(n, k, routed)
    local = (np.asarray(top_e) >= first) & (np.asarray(top_e) < first + held)
    if real is not None:
        local &= np.asarray(real)[:, None]
    sizes = np.bincount(np.asarray(top_e)[local] - first, minlength=held)
    assert int(stats[0]) == local.sum()
    assert int(stats[1]) == (sizes > 0).sum()
    assert int(stats[2]) == (-(-sizes // block)).sum()
    if case == "one_expert":
        assert int(stats[1]) == 1 and int(stats[2]) == -(-n // block) > 1
    if case == "nothing_real":
        assert not np.asarray(stats).any() and not np.asarray(got).any()
    # the call is sized for the worst case, so some tiles are always idle
    assert int(stats[2]) < ge.tiles_max(n * k, held, block)


@pytest.mark.parametrize("stacked", [True, False], ids=["stacks", "tuples"])
@pytest.mark.parametrize("case", ["random", "skewed", "half_masked"])
def test_the_loop_reads_stacks_and_tuples_alike(case, stacked):
    """``experts[j][i]`` means the same for a stack and for a tuple of
    arrays: the loop (this backend's path) takes either."""
    n, k, E = 40, 2, 8
    rng = np.random.default_rng(7)
    y = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    ex = _weights(E, jnp.float32, stacked=stacked)
    top_e, real = _routing(case, n, k, E, rng)
    top_w = jnp.asarray(rng.uniform(0.2, 0.8, (n, k)), jnp.float32)
    got, stats = jax.jit(lambda y, e, w: dropless.routed(
        y, e, w, ex, first=0, held=E, n_routed=E, real=real))(
            y, top_e, top_w)
    dense = _dense(y, top_e, top_w, ex, 0, E, real)
    assert float(jnp.abs(got - dense).max()) < 1e-5
    assert stats.shape == (dropless.N_STATS,) == (3,)


def test_the_kernel_alone_writes_real_tiles_and_only_them():
    """The call itself: each real tile is its expert's three products,
    consecutive tiles of one expert included; tiles past the count keep
    what the interpreter put there (NaN)."""
    E, block, tiles = 4, 16, 7
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(tiles * block, D)), jnp.float32)
    ex = _weights(E, jnp.float32, seed=2)
    tile_expert = jnp.asarray([0, 2, 2, 2, 3, 3, 3], jnp.int32)
    out = ge.grouped_experts(xs, *ex, tile_expert, jnp.asarray([5]),
                             block=block,
                             interpret=pltpu.InterpretParams())
    out = np.asarray(out).reshape(tiles, block, D)
    for t in range(5):
        x, i = xs[t * block:(t + 1) * block], int(tile_expert[t])
        want = (jax.nn.silu(x @ ex[0][i]) * (x @ ex[1][i])) @ ex[2][i]
        np.testing.assert_allclose(out[t], np.asarray(want), atol=1e-5)
    assert np.isnan(out[5:]).all()


@pytest.mark.parametrize("n,block,tiles", [(512, 64, 190), (1024, 128, 191)],
                         ids=["dispatch", "prefill"])
def test_supports_takes_sdarmoes_shapes(n, block, tiles):
    sds = jax.ShapeDtypeStruct
    E, d, dff = 128, 2048, 768
    ex = (sds((E, d, dff), jnp.bfloat16), sds((E, d, dff), jnp.bfloat16),
          sds((E, dff, d), jnp.bfloat16))
    assert dropless.block_rows(n, 8, E) == block
    assert ge.tiles_max(n * 8, E, block) == tiles
    assert ge.supports(ex, block, jnp.bfloat16)
    # two experts' matrices and the row blocks: about 20 MB
    assert 18e6 < ge.vmem_bytes(d, dff, block, jnp.bfloat16) < 26e6


@pytest.mark.parametrize("why", ["cohere2moe", "tuples", "stored_wider",
                                 "lanes", "sublanes"])
def test_supports_refuses_by_shape(why):
    sds = jax.ShapeDtypeStruct
    bf, block = jnp.bfloat16, 64
    stack = lambda E, d, dff, dt=bf: (sds((E, d, dff), dt),
                                      sds((E, d, dff), dt),
                                      sds((E, dff, d), dt))
    if why == "cohere2moe":
        # 16 held experts of 4096 x 4096: 100 MB an expert, at its token
        # step's block and at its prefill's
        assert not ge.supports(stack(16, 4096, 4096), 32, bf)
        assert not ge.supports(stack(16, 4096, 4096), 512, bf)
    elif why == "tuples":
        # an array of its own per expert is the loop's layout
        one = stack(1, 2048, 768)
        ex = tuple(tuple(sds(w.shape[1:], bf) for _ in range(4))
                   for w in one)
        assert not ge.supports(ex, block, bf)
    elif why == "stored_wider":
        # float32 at rest under a bfloat16 forward: the loop casts
        assert not ge.supports(stack(8, 2048, 768, jnp.float32), block, bf)
    elif why == "lanes":
        assert not ge.supports(stack(8, 2048, 96), block, bf)
        assert not ge.supports(stack(8, 192, 768), block, bf)
    else:
        assert not ge.supports(stack(8, 256, 128), 8, bf)
        assert ge.supports(stack(8, 256, 128, jnp.float32), 8, jnp.float32)


def test_off_the_tpu_the_routine_takes_the_loop():
    """Routing is by backend, mesh and shape: here on the CPU the program
    holds no Mosaic call at the very shapes the kernel supports, so the
    serve tests' programs are the loop's."""
    n, k, E, d, dff = 64, 8, 128, 256, 128
    sds = jax.ShapeDtypeStruct
    ex = (sds((E, d, dff), jnp.bfloat16), sds((E, d, dff), jnp.bfloat16),
          sds((E, dff, d), jnp.bfloat16))
    block = dropless.block_rows(n, k, E)
    assert ge.supports(ex, block, jnp.bfloat16)
    assert not dropless._use_grouped_kernel(ex, block, jnp.bfloat16)
    assert dropless.path(ex, n, k, E, jnp.bfloat16) == "loop"
    text = jax.jit(lambda y, e, w, *ex: dropless.routed(
        y, e, w, ex, first=0, held=E, n_routed=E)).lower(
            sds((n, d), jnp.bfloat16), sds((n, k), jnp.int32),
            sds((n, k), jnp.float32), *ex).as_text()
    assert "custom_call" not in text
    assert "stablehlo.while" in text and "stablehlo.case" in text
