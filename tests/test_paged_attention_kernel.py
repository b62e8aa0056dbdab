"""The page-table-driven decode kernel (``ops/pallas/paged_attention.py``)
under the Pallas TPU interpreter, held to the masked read it stands in for
on the TPU (``transformer._masked_pool_read``, the CPU path): same inputs,
same answers, at tiny sizes. The interpreter raises on a copy out of
bounds and hands the kernel NaN for memory it never wrote, so "reads only
what the slot owns" is checked, not assumed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tpudist.models import transformer as T
from tpudist.ops.pallas import paged_attention as pa

L, PAGES, PT, HD, MAXP = 3, 20, 4, 8, 5
TRASH = PAGES


def _pool(kv, dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (L, kv, PAGES + 1, PT, HD)
    return (jnp.asarray(rng.normal(size=shape), dtype),
            jnp.asarray(rng.normal(size=shape), dtype))


def _table(first_pos, w, perm=None):
    """A row a slot: pages for positions ``0 .. first + w - 1`` (the
    window's writes have landed), ``None`` a slot with nothing mapped."""
    perm = list(range(PAGES)) if perm is None else list(perm)
    table = np.full((len(first_pos), MAXP), -1, np.int32)
    pos = np.zeros((len(first_pos), w), np.int32)
    for s, p in enumerate(first_pos):
        if p is None:
            continue
        pos[s] = p + np.arange(w)
        n = -(-(p + w) // PT)
        table[s, :n] = [perm.pop() for _ in range(n)]
    return table, pos


def _both(q, pool_k, pool_v, layer, table, pos, block_pages=None):
    """``pos``: the last key position each query row may read (its own
    position for a decode or verify window, its block's last for a block
    of a block-diffusion model: both reads take the bound as data)."""
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    layer = jnp.int32(layer)
    ref = T._masked_pool_read(q, pool_k, pool_v, layer, table, pos, PT)
    out = pa.paged_attention(
        q, pool_k, pool_v, layer, pa.walk(table, pos, PT, PAGES + 1),
        block_pages=block_pages, interpret=pltpu.InterpretParams())
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


def _q(slots, w, h, dtype, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(slots, w, h, HD)), dtype)


# (id, window, heads, kv heads, first query position a slot (None:
# nothing mapped), layer, pages a block (None: the kernel's choice, here a
# whole row), dtype)
CASES = [
    ("w1-group1", 1, 2, 2, [6, 13, 0, 17], 1, None, jnp.float32),
    ("w1-group2", 1, 4, 2, [6, 13, 0, 17], 1, None, jnp.float32),
    ("w1-group4", 1, 4, 1, [6, 13, 0, 17], 1, None, jnp.float32),
    ("w3-group1", 3, 2, 2, [6, 11, 0, 15], 1, None, jnp.float32),
    ("w3-group2", 3, 4, 2, [6, 11, 0, 15], 1, None, jnp.float32),
    ("w3-group4", 3, 4, 1, [6, 11, 0, 15], 1, None, jnp.float32),
    # the query sits on a page's last row, on the next page's first, on
    # its second: k * page_tokens - 1, k * page_tokens, k * page_tokens + 1
    ("page-edges", 1, 4, 2, [PT - 1, PT, PT + 1, 3 * PT - 1, 3 * PT,
                             3 * PT + 1], 1, None, jnp.float32),
    ("page-edges-w3", 3, 4, 2, [PT - 1, PT, PT + 1, 2 * PT - 3], 1, None,
     jnp.float32),
    # several blocks a slot: the online softmax across blocks, a block
    # cut short by the row's end, the next slot's first block started
    # behind a slot's last
    ("blocks-of-2", 1, 4, 2, [6, 13, 0, 19], 1, 2, jnp.float32),
    ("blocks-of-1-w3", 3, 4, 2, [6, 11, 0, 17], 1, 1, jnp.float32),
    ("blocks-of-3", 1, 4, 2, [19, None, 12, 1], 1, 3, jnp.float32),
    ("layer-first", 1, 4, 2, [6, 13, 0, 17], 0, 2, jnp.float32),
    ("layer-last", 1, 4, 2, [6, 13, 0, 17], L - 1, 2, jnp.float32),
    ("bfloat16", 1, 4, 2, [6, 13, 0, 17], 1, 2, jnp.bfloat16),
    ("bfloat16-w3", 3, 4, 2, [6, 11, 0, 15], 2, None, jnp.bfloat16),
    # a block of 4 positions a slot, every row bounded by the block's LAST
    # position (``block4-``: the bound is given apart from the positions):
    # blocks that start a page, end one, and lie inside one
    ("block4-group2", 4, 4, 2, [4, 12, 0, 16], 1, None, jnp.float32),
    ("block4-group4-blocks-of-2", 4, 4, 1, [8, 0, None, 16], 1, 2,
     jnp.float32),
    ("block4-bfloat16", 4, 4, 2, [4, 12, 0, 16], 2, 2, jnp.bfloat16),
    # the first forward of a block that carries the previous block's
    # commit: 8 rows a slot from the previous block's first position, rows
    # 0-3 bounded by that block's last position, rows 4-7 by their own
    # block's (``commit4-``): bounds on a page's edge and inside one
    ("commit4-group2", 8, 4, 2, [4, 8, 0, 12], 1, None, jnp.float32),
    ("commit4-group4-blocks-of-2", 8, 4, 1, [12, None, 0, 4], 1, 2,
     jnp.float32),
    ("commit4-bfloat16", 8, 4, 2, [4, 8, 0, 12], 2, 2, jnp.bfloat16),
    # ``cohere2moe``'s full layer: a group of 16 query heads a kv head,
    # rows that hold one or two of the row's pages beside a full one, a
    # freed slot (row all -1), blocks of 2 pages
    ("group16-kv2", 1, 32, 2, [0, 17, None, 5], 1, 2, jnp.float32),
    ("group16-kv8", 1, 128, 8, [2, None, 19, 0, 7], 1, 2, jnp.float32),
    ("group16-kv8-bfloat16", 1, 128, 8, [2, None, 19, 0, 7], 0, 2,
     jnp.bfloat16),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_matches_masked_read(case):
    _, w, h, kv, first_pos, layer, block_pages, dtype = case
    pool_k, pool_v = _pool(kv, dtype)
    perm = np.random.default_rng(2).permutation(PAGES)
    table, pos = _table(first_pos, w, perm)
    if case[0].startswith("block4-"):
        own, pos = pos, np.broadcast_to(pos[:, -1:], pos.shape)
    if case[0].startswith("commit4-"):
        # one bound a row: a block's end, the committed block's or its own
        own = np.broadcast_to(pos[:, -1:], pos.shape)
        pos = np.concatenate([np.broadcast_to(pos[:, 3:4], (len(pos), 4)),
                              own[:, 4:]], axis=1)
    out, ref = _both(_q(len(first_pos), w, h, dtype), pool_k, pool_v, layer,
                     table, pos, block_pages)
    if case[0].startswith("commit4-"):
        # read up to the current block's end, a committed row reads another
        # answer, and a current row the same
        one, _ = _both(_q(len(first_pos), w, h, dtype), pool_k, pool_v,
                       layer, table, own, block_pages)
        assert np.abs(one[0, 0] - out[0, 0]).max() > 1e-2
        np.testing.assert_allclose(
            one[0, 4:], out[0, 4:],
            atol=1e-5 if dtype == jnp.float32 else 3e-2)
    if case[0].startswith("block4-"):
        # the keys ahead inside the block count: bounded by its own
        # position, a block's first row reads another answer
        causal, _ = _both(_q(len(first_pos), w, h, dtype), pool_k, pool_v,
                          layer, table, own, block_pages)
        assert np.abs(causal[0, 0] - out[0, 0]).max() > 1e-2
        np.testing.assert_allclose(
            causal[0, -1], out[0, -1],
            atol=1e-5 if dtype == jnp.float32 else 3e-2)
    live = [s for s, p in enumerate(first_pos) if p is not None]
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert np.isfinite(out).all()
    for s, p in enumerate(first_pos):
        if p is None:       # nothing mapped: skipped, reads 0
            assert not out[s].any()


# the LATENT call: one pool whose rows are keys and, in their first
# ``v_width`` lanes, values; one kv "head" for all query heads; the scale an
# argument; the grid over groups of slots. (id, heads, first position a
# slot, pages a block, slots a grid step (None: all in one), dtype)
LATENT = [
    ("unequal-pages", 8, [6, 13, 0, 17], None, None, jnp.float32),
    ("an-empty-slot", 8, [19, None, 12, 1], 3, None, jnp.float32),
    ("groups-of-2", 8, [6, 13, 0, 17], 2, 2, jnp.float32),
    ("groups-of-1", 8, [9, None, 2, 14], 2, 1, jnp.float32),
    ("an-empty-group", 8, [None, None, 2, 14, 7, 3], 2, 2, jnp.float32),
    ("a-last-group-empty", 8, [5, 11, None, None], 1, 2, jnp.float32),
    ("bfloat16-groups", 16, [6, 13, 0, 17], 2, 2, jnp.bfloat16),
]
ROW, VW = 24, 16


@pytest.mark.parametrize("case", LATENT, ids=[c[0] for c in LATENT])
def test_latent_call_matches_masked_read(case):
    _, h, first_pos, block_pages, group, dtype = case
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(L, 1, PAGES + 1, PT, ROW)), dtype)
    table, pos = _table(first_pos, 1, rng.permutation(PAGES))
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    q = jnp.asarray(rng.normal(size=(len(first_pos), 1, h, ROW)), dtype)
    scale = 0.3                     # not ROW ** -0.5
    ref = T._masked_pool_read(q, pool, None, jnp.int32(1), table, pos, PT,
                              scale=scale, v_width=VW)
    out = pa.paged_attention(
        q, pool, None, jnp.int32(1), pa.walk(table, pos, PT, PAGES + 1),
        scale=scale, v_width=VW, block_pages=block_pages, slot_group=group,
        interpret=pltpu.InterpretParams())
    assert out.shape == ref.shape == (len(first_pos), 1, h, VW)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = [s for s, p in enumerate(first_pos) if p is not None]
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert np.isfinite(out).all()
    for s, p in enumerate(first_pos):
        if p is None:
            assert not out[s].any()
    # the scale is the argument's: the row's own width gives another answer
    other = T._masked_pool_read(q, pool, None, jnp.int32(1), table, pos, PT,
                                scale=ROW ** -0.5, v_width=VW)
    assert np.abs(np.asarray(other, np.float32)[live] - ref[live]).max() \
        > 1e-2


def test_slot_groups_keep_one_step_where_the_rows_fit():
    # every call before the latent one: one grid step
    assert pa.slot_groups(16, 16, 128, 128, jnp.bfloat16) == 16
    assert pa.slot_groups(128, 4 * 32, 128, 128, jnp.bfloat16) == 128
    # 192 slots x 64 heads x (640 + 512) lanes: 28 MB, in steps of 32
    assert pa.slot_groups(192, 64, 640, 512, jnp.bfloat16) == 32
    assert pa.slot_groups(7, 64, 640, 512, jnp.float32) == 7
    assert pa.slot_groups(191, 64, 640, 512, jnp.bfloat16) == 1


@pytest.mark.parametrize("row", ["unmapped", "stale-beyond-a-gap",
                                 "position-past-the-row"])
def test_discarded_slots_are_finite_and_in_bounds(row):
    """Slots the engine discards (inactive, outside the dispatch) keep
    whatever position and row they had: the kernel must stay in bounds
    (the interpreter raises otherwise) and finite, and the live slots
    beside them must not notice."""
    pool_k, pool_v = _pool(2, jnp.float32)
    table, pos = _table([6, 9, 13], 1)
    if row == "unmapped":
        table[1] = -1
    elif row == "stale-beyond-a-gap":
        table[1] = [table[1, 0], -1, 3, 3, -1]
        pos[1] = 18
    else:
        pos[1] = 10_000
    q = _q(3, 1, 4, jnp.float32)
    out, ref = _both(q, pool_k, pool_v, 1, table, pos, 2)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[[0, 2]], ref[[0, 2]], atol=1e-5)
    if row == "unmapped":
        assert not out[1].any()


def test_shared_prefix_page_named_by_two_rows():
    pool_k, pool_v = _pool(2, jnp.float32)
    table, pos = _table([9, 10, 5], 1)
    table[1, :2] = table[0, :2]             # two pages of shared prefix
    out, ref = _both(_q(3, 1, 4, jnp.float32), pool_k, pool_v, 1, table,
                     pos, 2)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("read", ["kernel", "masked-read"])
def test_pages_no_live_row_names_never_reach_the_answer(read):
    """The page-bounded property: every page that no live row names up to
    its position, the trash page included, poisoned in every layer. The
    kernel never copies them, so NaN keys AND NaN values leave its answer
    the clean pool's bit for bit. The masked read scores them all and
    masks: a NaN key is dropped by the select, but a value meets a
    probability of exactly 0 and 0 x NaN is NaN, so its values are
    poisoned with the largest finite number instead."""
    pool_k, pool_v = _pool(2, jnp.float32)
    table, pos = _table([6, None, 13, 2], 3,
                        np.random.default_rng(3).permutation(PAGES))
    q = _q(4, 3, 4, jnp.float32)
    named = {int(p) for p in table.ravel() if p >= 0}
    dead = np.array([p for p in range(PAGES + 1) if p not in named])
    assert TRASH in dead and len(dead) > 3
    bad_v = jnp.nan if read == "kernel" else jnp.finfo(jnp.float32).max
    bad_k = pool_k.at[:, :, dead].set(jnp.nan)
    bad_v = pool_v.at[:, :, dead].set(bad_v)
    clean_out, clean_ref = _both(q, pool_k, pool_v, 1, table, pos, 2)
    out, ref = _both(q, bad_k, bad_v, 1, table, pos, 2)
    if read == "kernel":
        np.testing.assert_array_equal(out, clean_out)
    else:
        live = [0, 2, 3]    # a slot with nothing owned averages the pool
        np.testing.assert_array_equal(ref[live], clean_ref[live])
        np.testing.assert_array_equal(out, clean_out)


def test_paged_attention_reads_up_to_see_and_writes_at_positions():
    """``transformer._paged_attention`` with ``see``: the new k/v land at
    ``positions``, every row reads up to ``see``. A block's first row then
    reads what a query at the block's last position reads."""
    pool_k, pool_v = _pool(2, jnp.float32)
    table, pos = _table([8, 4], 4)
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    q = _q(2, 4, 4, jnp.float32)
    new = _q(2, 4, 2, jnp.float32, seed=5)
    see = jnp.broadcast_to(pos[:, -1:], pos.shape)
    ok = jnp.ones(pos.shape, bool)
    out, pk, pv = T._paged_attention(q, new, new, pool_k, pool_v, 1, table,
                                     pos, ok, PT, see=see)
    plain, pk2, _ = T._paged_attention(q, new, new, pool_k, pool_v, 1,
                                       table, pos, ok, PT)
    assert np.array_equal(np.asarray(pk), np.asarray(pk2))   # same write
    want = T._masked_pool_read(q, pk, pv, jnp.int32(1), table, see, PT)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    # the last row's bound is its own position either way
    np.testing.assert_allclose(np.asarray(out[:, -1]),
                               np.asarray(plain[:, -1]), atol=1e-6)
    assert np.abs(np.asarray(out[:, 0]) - np.asarray(plain[:, 0])).max() \
        > 1e-2


def test_walk_counts_pages_and_links_live_slots():
    table = np.array([[4, 2, -1, -1], [-1, -1, -1, -1], [1, -1, 3, -1],
                      [0, 5, 6, 7]], np.int32)
    pos = np.array([[5, 6], [0, 1], [9, 10], [14, 15]], np.int32)
    n_pages, nxt, flat, flat_pos = pa.walk(jnp.asarray(table),
                                           jnp.asarray(pos), PT, 9)
    # slot 0: positions up to 6 -> 2 pages; slot 1: nothing mapped;
    # slot 2: wants 3, mapped without a gap 1; slot 3: all four
    assert n_pages.tolist() == [2, 0, 1, 4]
    assert nxt.tolist() == [0, 2, 2, 3, 4]
    assert flat.shape == (16,) and int(flat.min()) == 0
    assert flat_pos.tolist() == pos.ravel().tolist()


def test_supports_is_by_shape():
    pool = (24, 8, 257, 64, 128)
    assert pa.supports((16, 1, 16, 128), pool, jnp.bfloat16, 64)
    assert pa.supports((16, 5, 16, 128), pool, jnp.bfloat16, 64)
    assert pa.supports((16, 1, 16, 128), pool, jnp.float32, 8)
    # lanes not full; a page that is no whole tile of the dtype; query
    # rows that are no whole tile
    assert not pa.supports((16, 1, 16, 64), (24, 8, 257, 64, 64),
                           jnp.bfloat16, 64)
    assert not pa.supports((16, 1, 16, 128), pool, jnp.bfloat16, 8)
    assert not pa.supports((16, 1, 4, 128), (24, 2, 257, 64, 128),
                           jnp.bfloat16, 64)
    # values out of the key rows: a whole number of their lanes
    latent = (8, 1, 4097, 64, 640)
    assert pa.supports((192, 1, 64, 640), latent, jnp.bfloat16, 64, 512)
    assert not pa.supports((192, 1, 64, 640), latent, jnp.bfloat16, 64, 576)
    assert not pa.supports((192, 1, 64, 576), (8, 1, 4097, 64, 576),
                           jnp.bfloat16, 64, 512)
    assert pa.pages_per_block(latent, jnp.bfloat16, 32) == 8
    assert pa.pages_per_block(pool, jnp.bfloat16, 20) == 8
    assert pa.pages_per_block(pool, jnp.bfloat16, 3) == 3


def test_off_the_tpu_paged_attention_takes_the_masked_read():
    """Routing is by backend and shape: here on the CPU the decode
    programs hold no Mosaic call, at the very shapes the kernel supports,
    so the serve tests' programs are the ones they were."""
    s, w, h, kv, hd, pt = 2, 1, 8, 2, 128, 16
    assert pa.supports((s, w, h, hd), (2, kv, 5, pt, hd), jnp.bfloat16, pt)
    assert not T._use_paged_kernel((s, w, h, hd), (2, kv, 5, pt, hd),
                                   jnp.bfloat16, pt)
    sds = jax.ShapeDtypeStruct
    pool = sds((2, kv, 5, pt, hd), jnp.bfloat16)
    text = jax.jit(T._paged_attention, static_argnums=(9,)).lower(
        sds((s, w, h, hd), jnp.bfloat16), sds((s, w, kv, hd), jnp.bfloat16),
        sds((s, w, kv, hd), jnp.bfloat16), pool, pool, sds((), jnp.int32),
        sds((s, 3), jnp.int32), sds((s, w), jnp.int32), sds((s, w), bool),
        pt).as_text()
    assert "tpu_custom_call" not in text and "custom_call" not in text
    assert "dynamic_slice" in text      # the layer's whole page set
