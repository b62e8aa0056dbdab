"""The prefill/decode-split serving engine: exactly TWO compiled programs
(three with speculation on), over a paged KV pool. A model that generates
by diffusion over blocks (``ModelConfig.block_length``) gets the same two:
its prefill writes the prompt's whole blocks and yields no token, and its
dispatch is the block-denoising program (``_paged_denoise_body``), chosen
here by what the model declares as ``windowed`` chooses the ring programs.
A model with latent attention (``ModelConfig.kv_lora_rank``) gets the same
two over ONE pool of latent rows (``latent``: ``_latent_prefill`` seeds it
from every attention sublayer's rows, the decode step carries it alone;
``PagedServeState.pool_v`` is None), and samples through its own untied
head.

The pjit/TPUv4 discipline that keeps the training loop honest (one
compiled program per run, traced scalars for everything that varies)
applies doubly to serving, where continuous batching changes the live
request set every few milliseconds: a recompile per admission would
bury the latency SLO. So the engine compiles exactly two programs and
pins it (``prefill.traces`` / ``decode.traces``, asserted in tests and
the CI lane):

* **prefill** — one request into one slot: full causal forward over the
  padded prompt (the model's ``prefill_kv_hidden_states`` hands back
  every layer's rotated K/V, copied into the slot's pages a page at a
  time), first token by greedy argmax at the prompt's true last
  position. Slot index, prompt length, the generation budget and the
  slot's page-table row are traced; the prompt is padded to a fixed
  ``prompt_pad`` so every admission reuses the one program.
* **decode** — a ``lax.scan`` superstep of ``decode_k`` steps over the
  WHOLE slot batch. Per-slot active masks (``jnp.where`` on every state
  update) keep finished/empty slots frozen, and a ``lax.cond`` skips an
  iteration outright when NO slot is active (mid-scan completion of the
  last request — the same masking discipline that kept PR 2's padded
  superstep bitwise) — so one compiled program serves every batch
  occupancy from full to empty. Each step is the model's
  ``paged_hidden_states`` over the pool, which rides the layer loop as
  its carry and is written in place. Which READ of the pool runs is the
  model's routing by backend and shape
  (``transformer._use_paged_kernel``), never a flag of this engine: on
  one TPU chip at kernel-sized shapes (``head_dim`` a multiple of 128,
  pages a whole tile) each slot's queries go through the Pallas kernel
  ``ops/pallas/paged_attention.py``, which copies only the pages the
  slot's own row of the page table maps up to its position; on the
  CPU, on a multi-chip mesh and at any other shape the XLA masked read
  (``transformer._masked_pool_read``) stages the layer's whole page set
  and masks what a slot does not own. ``cohere2moe`` has a read of its
  own for its two kinds of cache; ``longcatflash`` hands the kernel (and
  off the chip the masked read) its one latent pool, the width of the
  values inside a row and its own softmax scale
  (``longcatflash.latent_paged_attention``). The verify program runs the
  same read at window ``speculate_k``; over a latent cache it is refused
  in words, as is a shared prefix.

An expert model's routed sum is ``models/dropless.routed`` in every
program; whether it runs a layer's experts as one grouped Pallas kernel
or as its loop of blocks is that routine's own routing by backend, mesh
and shape, which the engine only reports (``experts_paths``; once a
traced run as the ``experts_path`` instant, beside the blocks of rows
each dispatch ran in ``read_stats``' ``moe_blocks``). ``read_stats`` hands
back the routine's three counts and, under the model's own names
(``EXTRA_STATS``), whatever the model counts beyond them (``longcatflash``:
pairs on identity experts and all pairs routed).

With graceful degradation on (``adapt_ladder``), the contract
generalises to one decode program PER LADDER RUNG, all compiled at
warmup: a pressure downshift switches programs, it never traces one.

Greedy decoding is a pure function of (params, state), so runs are
bitwise reproducible; decode-over-pages logits are pinned ULP-close to
the full forward (tests/test_serve.py), and the kernel is held to the
masked read (tests/test_paged_attention_kernel.py under the Pallas
interpreter; tests_tpu/test_tpu_lane.py on the chip).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudist.config import ModelConfig
from tpudist.engine import OnMesh, _arg_specs
from tpudist.models import dropless, model_for
from tpudist.obs import trace as trace_lib
from tpudist.parallel import sharding as shd
from tpudist.scopes import cast, scope, scoped
from tpudist.serve import kvcache
from tpudist.utils import compat

# the one block length the block-denoising program is built and held at
BLOCK_BUILT = 4


class PagedServeState(NamedTuple):
    """Device-resident serving state — the scan carry of the decode
    superstep and the donation target of every program. K/V live in one
    shared pool of fixed-size pages (+1 trash page); the slot→page
    mapping is HOST state (``PageAllocator.table``), passed into every
    dispatch as a small traced int32 array."""

    pool_k: jax.Array        # (L, kv, pages+1, page_tokens, head_dim)
    pool_v: Optional[jax.Array]     # None: a latent cache is ONE pool,
    # (sublayers, 1, pages+1, page_tokens, latent_row), in ``pool_k``
    lengths: jax.Array       # (slots,) int32: tokens in cache per slot
    last_token: jax.Array    # (slots,) int32: newest token, not yet cached
    active: jax.Array        # (slots,) bool: slot holds a live sequence
    remaining: jax.Array     # (slots,) int32: generation budget left
    # a model with window layers only (else empty / None): one ring per
    # window layer, (kv, slots, ring_tokens, head_dim) each, and what the
    # LAST program run counted: [token steps run, the model's own counts]
    ring_k: tuple = ()
    ring_v: tuple = ()
    stats: Optional[jax.Array] = None
    # a model that generates by diffusion over blocks only (else None):
    # the slot's CURRENT block, (slots, block_length) each: its tokens
    # (the prompt's remainder as given, the mask token elsewhere), which
    # of its positions are still masked, and for the block the LAST
    # dispatch finished the denoising step at which each position was
    # unmasked (-1: it was given)
    block_tok: Optional[jax.Array] = None
    block_open: Optional[jax.Array] = None
    block_step: Optional[jax.Array] = None



def init_params(model_cfg: ModelConfig, mesh, seed: int = 0):
    """Seeded model params placed to their sanitised param_specs layout
    — the same init + sharding recipe the training engine uses, minus
    the optimizer state serving has no use for. The dtype is the
    model's own (``model.init``'s float32, or what a leafwise init makes
    in place): the ENGINE owns the dtype at rest and converts a tree it
    is handed once (``PagedServeEngine._resident``)."""
    model = model_for(model_cfg)
    if getattr(model, "LEAFWISE_INIT", False):
        # a model whose float32 whole does not fit the chip that serves
        # it: every leaf is made where it will live, in its dtype at rest
        return model.init(jax.random.PRNGKey(seed), model_cfg,
                          sharding=shd.replicated(mesh))
    params = model.init(jax.random.PRNGKey(seed), model_cfg)
    shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), model_cfg))
    pspecs = shd.sanitize_specs(shape, model.param_specs(model_cfg), mesh)
    return jax.device_put(params, shd.named(mesh, pspecs))


class PagedServeEngine:
    """The serving engine: paged KV pool, shared prefixes, speculation.

    Builds and owns the compiled programs plus the state layout — ONE
    prefill program, one decode program per ladder rung and, when
    speculation is on, one more pinned program: the VERIFY forward, a
    single batched target forward over a ``speculate_k``-token window
    per slot that scores a whole host-proposed draft at once. Page
    table and per-dispatch active mask ride as small traced arrays
    (fixed shapes → no retrace); admission, eviction, page exhaustion
    and drafting are pure host decisions between dispatches.

    ``prompt_pad`` is the static prompt width every admission pads to;
    ``decode_k`` the superstep length (tokens per dispatch per slot);
    ``page_tokens`` / ``pages`` size the KV pool
    (:mod:`tpudist.serve.kvcache`; ``pages=0`` is full capacity, a pool
    no admission ever waits for).

    ``adapt_ladder`` is the graceful-degradation rung set
    (:func:`tpudist.serve.resilience.default_ladder`): ONE decode
    program is compiled per distinct ``k`` at warmup, so the pressure
    controller downshifting mid-run switches to an already-compiled
    program — the latency SLO never pays a recompile for degrading.
    The default ladder is ``(decode_k,)``, which keeps the original
    two-program contract bit-for-bit.

    ``speculate_k`` is the verify WINDOW width: the window carries the
    slot's pending ``last_token`` plus ``speculate_k - 1`` draft tokens,
    so ``speculate_k >= 2`` turns speculation on (a window of 1 is
    plain decode) and ``0`` turns it off. Greedy token output is
    bitwise-identical to non-speculative greedy decode by construction:
    every emitted token is the argmax after a verified-correct token,
    and rejected drafts' junk KV sits at positions beyond the new
    length, where write-then-attend overwrites it before any query can
    attend it.

    The weights rest on the device in ``dtype``: every public method
    that takes ``params`` hands the programs ``_resident(params)``, the
    tree with each floating leaf of another dtype converted once. The
    engine keeps the LAST tree it was handed beside its converted twin,
    on the instance and nowhere else, until another tree arrives
    (swapped weights convert again) or the engine is dropped (both go
    with it); a tree already in ``dtype`` is its own twin and costs
    nothing.
    """

    def __init__(self, model_cfg: ModelConfig, mesh, *, slots: int,
                 max_seq: int, prompt_pad: int, decode_k: int = 8,
                 page_tokens: int = 8, pages: int = 0,
                 speculate_k: int = 0, dtype=jnp.float32,
                 adapt_ladder: Optional[Sequence[int]] = None,
                 ring_margin: int = 128):
        if slots < 1:
            raise ValueError(f"--slots must be >= 1, got {slots}")
        if decode_k < 1:
            raise ValueError(
                f"--decode-steps-per-dispatch must be >= 1, got {decode_k}")
        if not 0 < prompt_pad <= max_seq:
            raise ValueError(
                f"prompt_pad {prompt_pad} must be in (0, max_seq "
                f"{max_seq}]")
        if speculate_k == 1 or speculate_k < 0:
            raise ValueError(
                f"--speculate-k must be 0 (off) or >= 2 (window of "
                f"last_token + drafts), got {speculate_k}")
        self.model_cfg = model_cfg
        self.model = model_for(model_cfg)
        self.mesh = mesh
        self.slots, self.max_seq = int(slots), int(max_seq)
        # a model that declares a block length generates by diffusion over
        # blocks: its dispatch is a program of its own below (a block
        # through its denoising forwards and the commit forward), its
        # prefill yields no token, and a dispatch's token steps are the
        # block's positions
        self.block = int(model_cfg.block_length)
        if not self.block and getattr(self.model, "GENERATES_BY_BLOCKS",
                                      False):
            raise ValueError(
                f"model {model_cfg.name!r} generates by diffusion over "
                f"blocks and has no token-a-step decode: give its config "
                f"a block_length")
        # its denoising forwards (one position unmasked a step) + the commit
        self.forwards_per_block = self.block + 1 if self.block else 0
        if self.block:
            self._check_block(model_cfg, speculate_k, adapt_ladder)
            decode_k = self.block
        self.prompt_pad, self.decode_k = int(prompt_pad), int(decode_k)
        ladder = tuple(int(k) for k in (adapt_ladder or (decode_k,)))
        if not ladder or ladder[0] != self.decode_k:
            raise ValueError(
                f"adapt_ladder {ladder} must start at decode_k "
                f"{self.decode_k} (level 0 = full service)")
        if any(k < 1 for k in ladder) \
                or any(a <= b for a, b in zip(ladder, ladder[1:])):
            raise ValueError(
                f"adapt_ladder {ladder} must be strictly descending "
                f"positive superstep lengths")
        self.ladder = ladder
        self.dtype = dtype
        self.speculate_k = int(speculate_k)
        self.spec = kvcache.PagedCacheSpec.from_model(
            model_cfg, slots=slots, max_seq=max_seq,
            page_tokens=page_tokens, pages=pages, dtype=dtype,
            ring_margin=ring_margin)
        # two kinds of cache state: the programs of such a model are
        # bodies of their own below, the others' are untouched
        self.windowed = self.spec.window_layers > 0
        # a third kind: one latent row a token an attention sublayer, no V
        # pool; its prefill seeds and its decode step reads that one pool
        self.latent = self.spec.latent
        # a model whose programs count what they did (``read_stats``)
        self.counted = hasattr(self.model, "N_STATS")
        self._path_said = False     # the ``experts_path`` instant is out
        if self.windowed and self.speculate_k:
            raise ValueError(
                "--speculate-k over a model with window layers is not "
                "built: a rejected draft would have to be unwound from "
                "the ring")
        if self.latent and self.speculate_k:
            raise ValueError(
                "--speculate-k over a latent cache is not built: the "
                "verify forward has no absorbed form over a window of "
                "drafts that tests or the chip hold")
        self.alloc = kvcache.PageAllocator(self.spec)
        self.prefill_traces: list = []
        self.decode_traces: list = []
        self.verify_traces: list = []
        # the last params tree handed in, and what the programs get for it
        self._resident_of = self._resident_tree = None
        # per-program lowering skeletons, captured at each program's
        # first call (program_memory / the memledger's per-program
        # memory_analysis reads these off the request clock)
        self._programs: dict = {}
        self._prefill = OnMesh(
            jax.jit(self._paged_prefill_body, donate_argnums=(1,)), mesh)
        # k is STATIC (it is the lax.scan length): one compiled decode
        # program per ladder rung, all traced at warmup
        self._decode = OnMesh(
            jax.jit(self._paged_decode_body, static_argnums=(2,),
                    donate_argnums=(1,)), mesh)
        self._verify = OnMesh(
            jax.jit(self._paged_verify_body, donate_argnums=(1,)), mesh)
        self._denoise = OnMesh(
            jax.jit(self._paged_denoise_body, donate_argnums=(1,)), mesh)

    def _check_block(self, cfg: ModelConfig, speculate_k, adapt_ladder):
        b = self.block
        if speculate_k:
            raise ValueError(
                "--speculate-k with a model that generates by diffusion "
                "over blocks is not built: a dispatch already yields a "
                "block of tokens a slot, and there is no next-token draft "
                "to verify")
        if b != BLOCK_BUILT:
            raise ValueError(
                f"block_length {b} is not built: the flash mask, the "
                f"denoising scan and the paged read are held by tests and "
                f"by the chip at blocks of {BLOCK_BUILT} alone")
        if cfg.denoise_steps not in (0, b):
            raise ValueError(
                f"denoise_steps {cfg.denoise_steps} over blocks of {b} is "
                f"not built: the static schedule unmasks one position a "
                f"step, so a block takes block_length steps")
        if self.max_seq % b:
            raise ValueError(
                f"max_seq {self.max_seq} must be a whole number of blocks "
                f"of {b}: blocks are aligned to absolute positions")
        if adapt_ladder and tuple(adapt_ladder) != (b,):
            raise ValueError(
                f"adapt_ladder {tuple(adapt_ladder)} with a model that "
                f"generates by blocks of {b}: a dispatch is one block, "
                f"there is no shorter superstep to degrade to")

    # --------------------------------------------------------- weights

    def _resident(self, params):
        """``params`` as the programs take it: every floating
        ``jax.Array`` leaf whose dtype is not the engine's is converted
        on the device, leaf by leaf and with its sharding kept, so that
        ``scopes.cast`` inside the programs is a no-op and they emit no
        convert of a weight. Every other leaf is handed back as the same
        object (no copy), and a tree with nothing to convert as itself.
        The result is remembered for that tree by identity (and is its
        own result): the hot path pays two ``is``."""
        if params is self._resident_of or params is self._resident_tree:
            return self._resident_tree
        leaves, treedef = jax.tree_util.tree_flatten(params)
        todo = [i for i, w in enumerate(leaves)
                if isinstance(w, jax.Array) and w.dtype != self.dtype
                and jnp.issubdtype(w.dtype, jnp.floating)]
        tree = params
        if todo:
            itemsize = jnp.dtype(self.dtype).itemsize
            with trace_lib.get().span(
                    "weights_resident", cat="serve", leaves=len(leaves),
                    leaves_cast=len(todo),
                    bytes_in=sum(leaves[i].nbytes for i in todo),
                    bytes_out=sum(leaves[i].size * itemsize
                                  for i in todo)):
                for i in todo:
                    leaves[i] = leaves[i].astype(self.dtype)
                jax.block_until_ready([leaves[i] for i in todo])
            tree = treedef.unflatten(leaves)
        self._resident_of, self._resident_tree = params, tree
        return tree

    def new_allocator(self) -> kvcache.PageAllocator:
        """Fresh page bookkeeping (drops any shared-prefix registry) —
        one allocator per serve run, like one state per run."""
        self.alloc = kvcache.PageAllocator(self.spec)
        return self.alloc

    # ----------------------------------------------------------- state

    def init_state(self) -> PagedServeState:
        cache = kvcache.init_paged_cache(self.spec, self.mesh)
        rings = kvcache.init_rings(self.spec, self.mesh)
        rep = shd.replicated(self.mesh)
        vec = lambda v: jax.device_put(v, rep)
        s = self.slots
        return PagedServeState(
            pool_k=cache["k"], pool_v=cache["v"],
            lengths=vec(jnp.zeros((s,), jnp.int32)),
            last_token=vec(jnp.zeros((s,), jnp.int32)),
            active=vec(jnp.zeros((s,), bool)),
            remaining=vec(jnp.zeros((s,), jnp.int32)),
            ring_k=rings["k"], ring_v=rings["v"],
            stats=vec(jnp.zeros((1 + self.model.N_STATS,), jnp.int32))
            if self.counted else None,
            **({"block_tok": vec(jnp.zeros((s, self.block), jnp.int32)),
                "block_open": vec(jnp.zeros((s, self.block), bool)),
                "block_step": vec(jnp.full((s, self.block), -1, jnp.int32))}
               if self.block else {}))

    # --------------------------------------------------------- prefill

    def _tied_logits(self, params, h):
        with scope("lm_head"):
            emb = cast(params["embed"], self.dtype)
            logits = (h @ emb.T).astype(jnp.float32)
            if self.model_cfg.logit_scale != 1.0:
                logits = logits * self.model_cfg.logit_scale
            return logits

    def _greedy(self, params, h):
        """Greedy next token from final-normed hidden states, through the
        model's own head where it has one (untied), else the tied one."""
        if hasattr(self.model, "head_logits"):
            logits = self.model.head_logits(params, h, self.dtype)
        else:
            logits = self._tied_logits(params, h)
        with scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    @scoped("prefill")
    def _paged_prefill_body(self, params, state: PagedServeState,
                            tokens, prompt_len, slot, max_new, page_row,
                            shared_len
                            ) -> Tuple[PagedServeState, jax.Array]:
        self.prefill_traces.append(1)   # trace-time compile marker
        spec = self.spec
        if self.block:
            return self._block_prefill(params, state, tokens, prompt_len,
                                       slot, max_new, page_row, shared_len)
        # the model's full causal forward over the padded prompt, its
        # rotated K/V kept in a throwaway scratch row
        # (``prefill_kv_hidden_states``) — then copy the prompt's pages
        # into the slot's pages, a whole page at a time
        # (``_scatter_pages``). A model with window layers hands back
        # every layer's k/v itself and seeds both kinds of state
        # (``_windowed_prefill``); one with latent attention every
        # sublayer's rows (``_latent_prefill``).
        if self.windowed:
            h, cache = self._windowed_prefill(params, state, tokens,
                                              prompt_len, slot, page_row,
                                              shared_len)
        elif self.latent:
            h, cache = self._latent_prefill(params, state, tokens,
                                            prompt_len, page_row,
                                            shared_len)
        else:
            scratch_shape = (spec.n_layers, 1, self.prompt_pad,
                             spec.n_kv_heads, spec.head_dim)
            scratch = {"k": jnp.zeros(scratch_shape, self.dtype),
                       "v": jnp.zeros(scratch_shape, self.dtype)}
            h, scratch = self.model.prefill_kv_hidden_states(
                params, tokens, self.model_cfg, dtype=self.dtype,
                kv_cache=scratch)
        h_last = lax.dynamic_index_in_dim(h, prompt_len - 1, axis=1,
                                          keepdims=False)
        first = self._greedy(params, h_last)[0]
        if not (self.windowed or self.latent):
            with scope("kv_scatter"):
                pk, pv = self._scatter_pages(
                    state.pool_k, state.pool_v, scratch["k"], scratch["v"],
                    page_row, prompt_len, shared_len)
            cache = {"pool_k": pk, "pool_v": pv}
        rem = max_new - 1            # the prefill itself produced token 1
        active = (rem > 0) & (prompt_len < self.max_seq)
        return state._replace(
            lengths=state.lengths.at[slot].set(prompt_len),
            last_token=state.last_token.at[slot].set(first),
            active=state.active.at[slot].set(active),
            remaining=state.remaining.at[slot].set(
                jnp.where(active, rem, 0)), **cache), first

    def _scatter_pages(self, pool_k, pool_v, k, v, page_row, prompt_len,
                       shared_len):
        """A prompt's K/V, (L, 1, prompt_pad, kv, hd) of the pool's
        layers, copied into the slot's pages (a latent cache: its one
        pool and its rows, ``pool_v`` and ``v`` None; a tuple of one comes
        back). Pages wholly below
        ``shared_len`` are skipped (they are the shared prefix, already
        holding bitwise-identical content); those, the pages past the
        prompt and unmapped ones route to the trash page. The prompt's
        last page carries pad-token junk past ``prompt_len``: positions
        beyond the slot's length, which the decode mask (keys <= pos)
        never reads and write-then-attend overwrites first."""
        spec = self.spec
        pt = spec.page_tokens
        n_pp = -(-self.prompt_pad // pt)         # pages of a prompt
        start = jnp.arange(n_pp) * pt
        write = (start < prompt_len) & (start + pt > shared_len)
        pg = page_row[:n_pp]
        pg = jnp.where(write & (pg >= 0), pg, spec.pages)  # else: trash

        def page_blocks(c):
            # (L, 1, pad, kv, hd) -> per page (L, kv, 1, pt, hd)
            c = jnp.pad(c[:, 0], ((0, 0),
                                  (0, n_pp * pt - self.prompt_pad),
                                  (0, 0), (0, 0)))
            c = c.reshape(spec.n_layers, n_pp, pt, spec.n_kv_heads,
                          spec.head_dim)
            return c.transpose(1, 0, 3, 2, 4)[:, :, :, None]

        pools = (pool_k,) if pool_v is None else (pool_k, pool_v)
        blocks = tuple(page_blocks(c) for c in (k, v)[:len(pools)])

        def put(j, pools):
            return tuple(
                lax.dynamic_update_slice(pool, b[j],
                                         (0, 0, pg[j], 0, 0))
                for pool, b in zip(pools, blocks))

        return lax.fori_loop(0, n_pp, put, pools)

    def _latent_prefill(self, params, state: PagedServeState, tokens,
                        prompt_len, page_row, shared_len):
        """The forward of a model with latent attention (the expanded
        form over the whole prompt), and the one pool seeded from the rows
        it hands back, every attention sublayer's into the slot's pages."""
        h, rows, counts = self.model.prefill_hidden_states(
            params, tokens, self.model_cfg, dtype=self.dtype,
            prompt_len=prompt_len)
        with scope("kv_scatter"):
            # (sublayers, 1, pad, row) -> the pool's one "kv head"
            pk, = self._scatter_pages(
                state.pool_k, None, jnp.stack(rows)[:, :, :, None, :], None,
                page_row, prompt_len, shared_len)
        return h, {"pool_k": pk, "stats": jnp.concatenate(
            [jnp.ones((1,), jnp.int32), counts])}

    def _windowed_prefill(self, params, state: PagedServeState, tokens,
                          prompt_len, slot, page_row, shared_len):
        """The forward of a model with window layers, and both kinds of
        state seeded from the k/v it hands back: the full layers' into
        the slot's pages, as above; of each window layer the prompt's
        TAIL into the slot's ring, position p at ``p mod ring`` (entry r
        takes the newest position below ``prompt_len`` congruent to r;
        an entry no position reaches yet keeps row 0's bytes, which no
        query reads: its logical position is negative)."""
        h, ks, vs, counts = self.model.prefill_hidden_states(
            params, tokens, self.model_cfg, dtype=self.dtype,
            prompt_len=prompt_len)
        kinds = self.model.layer_kinds(self.model_cfg)
        full = [i for i, kind in enumerate(kinds) if kind == "full"]
        ring = self.spec.ring_tokens
        with scope("kv_scatter"):
            pk, pv = self._scatter_pages(
                state.pool_k, state.pool_v,
                jnp.stack([ks[i] for i in full]),
                jnp.stack([vs[i] for i in full]),
                page_row, prompt_len, shared_len)
            last = prompt_len - 1
            src = jnp.clip(last - jnp.mod(last - jnp.arange(ring), ring),
                           0, self.prompt_pad - 1)

            def tail(rings, xs):
                # (1, pad, kv, hd) -> (kv, 1, ring, hd) at the slot
                return tuple(
                    lax.dynamic_update_slice(
                        r, jnp.take(x[0], src, axis=0).transpose(
                            1, 0, 2)[:, None].astype(r.dtype),
                        (0, slot, 0, 0))
                    for r, x in zip(rings, xs))
            window = [i for i, kind in enumerate(kinds) if kind != "full"]
            rk = tail(state.ring_k, [ks[i] for i in window])
            rv = tail(state.ring_v, [vs[i] for i in window])
        stats = jnp.concatenate([jnp.ones((1,), jnp.int32), counts])
        return h, {"pool_k": pk, "pool_v": pv, "ring_k": rk, "ring_v": rv,
                   "stats": stats}

    def _block_prefill(self, params, state: PagedServeState, tokens,
                       prompt_len, slot, max_new, page_row, shared_len):
        """The prefill of a model that generates by blocks: the prompt's
        WHOLE blocks through the block-causal forward, their k/v into the
        slot's pages; no token comes of it. The prompt's remainder
        (``prompt_len mod block``) is not prefilled: it opens the slot's
        current block, already unmasked, beside mask tokens. Hands back the
        number of blocks written, to fence on."""
        b = self.block
        whole = prompt_len // b * b
        _, ks, vs, counts = self.model.prefill_hidden_states(
            params, tokens, self.model_cfg, dtype=self.dtype,
            prompt_len=whole)
        with scope("kv_scatter"):
            pk, pv = self._scatter_pages(
                state.pool_k, state.pool_v, jnp.stack(ks), jnp.stack(vs),
                page_row, whole, shared_len)
        at = whole + jnp.arange(b, dtype=jnp.int32)
        given = lax.dynamic_slice(jnp.pad(tokens[0], (0, b)), (whole,), (b,))
        tok = jnp.where(at < prompt_len, given,
                        jnp.int32(self.model_cfg.mask_token_id))
        active = (max_new > 0) & (whole < self.max_seq)
        row = lambda a, v: lax.dynamic_update_slice(a, v[None], (slot, 0))
        return state._replace(
            pool_k=pk, pool_v=pv,
            lengths=state.lengths.at[slot].set(whole),
            active=state.active.at[slot].set(active),
            remaining=state.remaining.at[slot].set(
                jnp.where(active, max_new, 0)),
            block_tok=row(state.block_tok, tok),
            block_open=row(state.block_open, at >= prompt_len),
            stats=jnp.concatenate([jnp.ones((1,), jnp.int32), counts])), \
            whole // b

    def _note_program(self, name: str, jitted, args,
                      static_idx: Tuple[int, ...] = ()) -> None:
        """Remember how to ``.lower()`` one pinned program: shape/
        dtype/sharding skeletons of its first call's traced arguments
        (``engine._arg_specs`` — no buffer kept alive, the donation
        contract survives) with static arguments kept verbatim in
        place. A dict-membership check per call on the hot path,
        nothing more."""
        if name in self._programs:
            return
        statics = set(static_idx)
        dyn = iter(_arg_specs(tuple(
            a for i, a in enumerate(args) if i not in statics)))
        lower_args = tuple(a if i in statics else next(dyn)
                           for i, a in enumerate(args))
        self._programs[name] = (jitted, lower_args)

    def program_memory(self) -> dict:
        """``{program_name: memory_analysis dict}`` for every pinned
        program the run has called — prefill, each decode-ladder rung,
        the speculative verify. An empty dict per program on backends
        without memory planning (the memledger records the gap as a
        note); lowering hits jit's trace cache, so this is cheap and
        off the request clock."""
        out: dict = {}
        for name, (jitted, lower_args) in sorted(self._programs.items()):
            try:
                out[name] = compat.memory_analysis(
                    jitted.lower(*lower_args).compile())
            except Exception:
                out[name] = {}
        return out

    def prefill(self, params, state: PagedServeState, tokens,
                prompt_len: int, slot: int, max_new: int,
                page_row=None, shared_len: int = 0
                ) -> Tuple[PagedServeState, jax.Array]:
        """Admit one request into ``slot``. ``tokens`` is the padded
        (1, prompt_pad) prompt; scalars go in as traced int32 so every
        admission reuses the one compiled program, and so do the slot's
        page-table ROW (defaults to the allocator's current row for
        ``slot``) and the shared-prefix watermark ``shared_len``
        (``alloc.admit_shared_len``). Returns the updated state and the
        request's FIRST generated token (a device scalar — ``int()`` it
        to fence); a model that generates by blocks yields no token here,
        and the scalar is the number of whole blocks of the prompt
        written to the cache."""
        params = self._resident(params)
        tokens = jnp.asarray(tokens, jnp.int32).reshape(1, self.prompt_pad)
        if page_row is None:
            page_row = self.alloc.row(slot)
        page_row = jnp.asarray(page_row, jnp.int32).reshape(
            self.spec.max_pages_per_slot)
        args = (params, state, tokens, jnp.int32(prompt_len),
                jnp.int32(slot), jnp.int32(max_new), page_row,
                jnp.int32(shared_len))
        self._note_program("prefill", self._prefill, args)
        return self._prefill(*args)

    def register_prefix(self, params, state: PagedServeState,
                        prefix_tokens, prefix_len: int
                        ) -> PagedServeState:
        """Cache a shared system-prompt prefix ONCE, for every future
        admission: reserve its full pages (registry-held, refcounted)
        and fill them by running the ONE compiled prefill program —
        width ``prompt_pad``, ``max_new=1`` so the probe slot comes
        back inactive and its scalar entries are overwritten by the
        slot's real admission later. Causal masking makes the stored
        K/V bitwise-identical to what any full prompt starting with
        this prefix would compute for those positions. The partial tail
        page (``prefix_len % page_tokens`` positions) routes to trash
        here; admissions recompute it into their first private page —
        the copy-on-write fork, done eagerly by recomputation."""
        if self.latent:
            raise ValueError(
                "a shared prefix over a latent cache is not built: no test "
                "or chip run holds shared pages of latent rows yet")
        params = self._resident(params)
        pages = self.alloc.register_shared(prefix_len)
        if not pages:
            return state
        row = np.full((self.spec.max_pages_per_slot,), -1, np.int32)
        row[:len(pages)] = pages
        padded = np.zeros((self.prompt_pad,), np.int32)
        padded[:prefix_len] = np.asarray(prefix_tokens)[:prefix_len]
        state, first = self.prefill(params, state, padded,
                                    prefix_len, 0, 1,
                                    page_row=row, shared_len=0)
        jax.device_get(first)
        return state

    # ---------------------------------------------------------- decode

    @scoped("decode")
    def _paged_decode_body(self, params, state: PagedServeState, k: int,
                           page_table, dispatch_active
                           ) -> Tuple[PagedServeState, jax.Array,
                                      jax.Array]:
        self.decode_traces.append(k)    # trace-time compile marker
        slots = self.slots

        def step(st: PagedServeState, _):
            def run(st: PagedServeState):
                act = st.active & dispatch_active
                pos = jnp.minimum(st.lengths, self.max_seq - 1)
                step_args = dict(
                    dtype=self.dtype,
                    page_table=page_table, positions=pos[:, None],
                    write_ok=(act & (st.lengths < self.max_seq))[:, None],
                    page_tokens=self.spec.page_tokens)
                # K and V pools; a latent cache hands its one pool alone
                pools = dict(pool_k=st.pool_k, pool_v=st.pool_v)
                if self.windowed:
                    h, pk, pv, rk, rv, counts = \
                        self.model.paged_hidden_states(
                            params, st.last_token[:, None], self.model_cfg,
                            ring_k=st.ring_k, ring_v=st.ring_v, **pools,
                            **step_args)
                    cache = {"ring_k": rk, "ring_v": rv,
                             "stats": st.stats + jnp.concatenate(
                                 [jnp.ones((1,), jnp.int32), counts])}
                elif self.latent:
                    h, pk, counts = self.model.paged_hidden_states(
                        params, st.last_token[:, None], self.model_cfg,
                        pool=st.pool_k, **step_args)
                    pv = None
                    cache = {"stats": st.stats + jnp.concatenate(
                        [jnp.ones((1,), jnp.int32), counts])}
                else:
                    h, pk, pv = self.model.paged_hidden_states(
                        params, st.last_token[:, None], self.model_cfg,
                        **pools, **step_args)
                    cache = {}
                nxt = self._greedy(params, h[:, 0])
                new_len = jnp.where(act, st.lengths + 1, st.lengths)
                new_rem = jnp.where(act, st.remaining - 1, st.remaining)
                # slots OUTSIDE this dispatch (their page rows may be
                # stale) keep their activity untouched
                new_active = jnp.where(
                    dispatch_active,
                    act & (new_rem > 0) & (new_len < self.max_seq),
                    st.active)
                new_state = st._replace(
                    pool_k=pk, pool_v=pv, lengths=new_len,
                    last_token=jnp.where(act, nxt, st.last_token),
                    active=new_active, remaining=new_rem, **cache)
                return new_state, jnp.where(act, nxt, -1), act

            def skip(st: PagedServeState):
                return (st, jnp.full((slots,), -1, jnp.int32),
                        jnp.zeros((slots,), bool))

            st, tok, valid = lax.cond(
                (st.active & dispatch_active).any(), run, skip, st)
            return st, (tok, valid)

        if self.windowed or self.latent:
            # what THIS dispatch counts: token steps run, the model's own
            state = state._replace(stats=jnp.zeros(
                (1 + self.model.N_STATS,), jnp.int32))
        state, (toks, valid) = lax.scan(step, state, None, length=k)
        return state, toks, valid

    @scoped("denoise")
    def _paged_denoise_body(self, params, state: PagedServeState,
                            page_table, dispatch_active
                            ) -> Tuple[PagedServeState, jax.Array,
                                       jax.Array]:
        """One dispatch of a model that generates by blocks: every live
        slot's current block through ``block`` denoising forwards and the
        commit forward. A forward runs the block's tokens at their
        positions: each layer writes their k and v into the slot's pages
        (provisional until the commit) and reads the committed cache and
        the block's own keys, all of them (``see``: the block's last
        position). A denoising step takes, at each position still masked,
        the argmax token and its probability (float32 softmax), and
        unmasks the one of highest probability (ties: the lowest
        position); a slot with nothing left masked (a request's first
        block opens with the prompt's remainder) idles through the steps
        that are left. The commit forward runs the block's final tokens
        once more: what it writes is the K/V the cache keeps. A slot's
        budget is honoured to the token: the block's positions past it
        are computed and not emitted."""
        self.decode_traces.append(self.block)   # trace-time compile marker
        b, cfg = self.block, self.model_cfg
        act = state.active & dispatch_active
        start = state.lengths                   # the block's first position
        offs = jnp.arange(b, dtype=jnp.int32)[None, :]
        pos = jnp.minimum(start[:, None] + offs, self.max_seq - 1)
        see = jnp.broadcast_to(pos[:, -1:], pos.shape)
        write_ok = act[:, None] & (start[:, None] + offs < self.max_seq)
        open0 = state.block_open & act[:, None]

        def forward(tok, pk, pv):
            return self.model.paged_hidden_states(
                params, tok, cfg, dtype=self.dtype, pool_k=pk, pool_v=pv,
                page_table=page_table, positions=pos, write_ok=write_ok,
                see=see, page_tokens=self.spec.page_tokens)

        def step(carry, s):
            tok, still, at, pk, pv, counts = carry
            h, pk, pv, st = forward(tok, pk, pv)
            with scope("unmask"):
                logits = self.model.head_logits(params, h, self.dtype)
                best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                conf = jnp.exp(jnp.max(logits, axis=-1)
                               - jax.nn.logsumexp(logits, axis=-1))
                pick = jnp.argmax(jnp.where(still, conf, -1.0), axis=1)
                hit = still & (offs == pick[:, None])
                tok = jnp.where(hit, best, tok)
                at = jnp.where(hit, s, at)
            return (tok, still & ~hit, at, pk, pv, counts + st), None

        (tok, _, at, pk, pv, counts), _ = lax.scan(
            step, (state.block_tok, open0, jnp.full_like(state.block_tok, -1),
                   state.pool_k, state.pool_v,
                   jnp.zeros((self.model.N_STATS,), jnp.int32)),
            jnp.arange(b, dtype=jnp.int32))
        with scope("commit"):
            _, pk, pv, st = forward(tok, pk, pv)
        # the new positions in order, as far as the budget goes
        emit = open0 & (jnp.cumsum(open0, axis=1) <= state.remaining[:, None])
        new_len = jnp.where(act, start + b, start)
        new_rem = jnp.where(act, state.remaining - emit.sum(axis=1),
                            state.remaining)
        new_active = jnp.where(
            dispatch_active,
            act & (new_rem > 0) & (new_len < self.max_seq), state.active)
        fresh = act[:, None]
        new_state = state._replace(
            pool_k=pk, pool_v=pv, lengths=new_len, active=new_active,
            remaining=new_rem,
            block_tok=jnp.where(fresh, jnp.int32(cfg.mask_token_id),
                                state.block_tok),
            block_open=jnp.where(fresh, True, state.block_open),
            block_step=jnp.where(fresh, at, -1),
            stats=jnp.concatenate([
                jnp.full((1,), self.forwards_per_block, jnp.int32),
                counts + st]))
        return new_state, jnp.where(fresh, tok, -1).T, emit.T

    def read_stats(self, state: PagedServeState) -> dict:
        """What the program that produced ``state`` counted, as span
        arguments (empty for a model that counts nothing). Call it after
        the fence on that program's tokens: the counts are ready then."""
        if state.stats is None:
            return {}
        steps, pairs, hit, blocks, *more = (int(v) for v in
                                            np.asarray(state.stats))
        steps = max(steps, 1)       # token steps, or a block's forwards
        cfg = self.model_cfg
        held = cfg.n_experts_held or cfg.n_experts
        # blocks over experts hit: the share of second trips to an expert
        out = {"moe_pairs_local": pairs,
               "moe_pairs_per_expert": pairs / (
                   held * cfg.n_layers * steps),
               "moe_experts_hit": hit / (cfg.n_layers * steps),
               "moe_blocks": blocks / (cfg.n_layers * steps)}
        # what the model counts beyond the routine's three, under its own
        # names (``longcatflash``: pairs on identity experts, all pairs)
        for name, v in zip(getattr(self.model, "EXTRA_STATS", ()), more):
            out[name] = v / (cfg.n_layers * steps)
        return out

    def experts_paths(self, params) -> dict:
        """Which way ``models/dropless.routed`` lowers this engine's
        programs, by the routine's own rule inside this engine's mesh:
        ``path`` the dispatch program's (a token step or a block a slot),
        ``prefill`` the prefill's; each ``grouped`` or ``loop``."""
        cfg, lp = self.model_cfg, params["layers"][0]
        experts = (lp["e_gate"], lp["e_up"], lp["e_down"])
        with jax.set_mesh(self.mesh):
            of = lambda n: dropless.path(
                experts, n, cfg.expert_top_k,
                cfg.n_experts + cfg.n_zero_experts, self.dtype)
            return {"path": of(self.slots * (self.block or 1)),
                    "prefill": of(self.prompt_pad)}

    def read_block(self, state: PagedServeState) -> np.ndarray:
        """(block, slots): the denoising step at which each position of
        the block the last dispatch finished was unmasked (-1: given, or
        the slot was outside the dispatch), in ``decode``'s orientation.
        Call it after the fence on that dispatch's tokens."""
        return np.asarray(state.block_step).T

    def decode(self, params, state: PagedServeState,
               k: Optional[int] = None, dispatch_active=None
               ) -> Tuple[PagedServeState, jax.Array, jax.Array]:
        """One decode superstep: up to ``k`` (default ``decode_k``)
        tokens for every active slot of the dispatch. ``k`` must be a
        warmed ladder rung. The CURRENT page table (the host
        allocator's) and the dispatch's slot mask go in as small traced
        int32/bool arrays — fixed shapes, so every dispatch reuses the
        rung's one compiled program. Returns ``(state, tokens (k,
        slots), valid (k, slots))`` — entries with ``valid=False`` are
        placeholders (-1) and must not be read. Async: fence on the
        returned tokens."""
        params = self._resident(params)
        k = self.decode_k if k is None else int(k)
        if k not in self.ladder:
            # fail at the fault site: a foreign k would silently trace
            # a NEW program mid-run — charging XLA compilation to
            # exactly the latency a downshift is trying to relieve —
            # and only surface at the end-of-run program pin, if ever
            raise ValueError(
                f"decode k={k} is not a warmed ladder rung "
                f"{self.ladder}")
        table = jnp.asarray(self.alloc.table, jnp.int32)
        if dispatch_active is None:
            da = jnp.ones((self.slots,), bool)
        else:
            da = jnp.asarray(dispatch_active, bool).reshape(self.slots)
        if self.counted and not self._path_said \
                and trace_lib.get().enabled:
            # once a traced run: whether the grouped kernel engaged at all
            self._path_said = True
            trace_lib.get().instant("experts_path", cat="serve",
                                    **self.experts_paths(params))
        if self.block:
            # tokens (block, slots): every position the dispatch computed
            # (-1 outside it); valid: the ones emitted. ``state.block_step``
            # says at which step each was unmasked
            self._note_program(f"denoise_b{k}", self._denoise,
                               (params, state, table, da))
            return self._denoise(params, state, table, da)
        self._note_program(f"decode_k{k}", self._decode,
                           (params, state, k, table, da), static_idx=(2,))
        return self._decode(params, state, k, table, da)

    # ---------------------------------------------------------- verify

    @scoped("decode")
    def _paged_verify_body(self, params, state: PagedServeState, draft,
                           page_table, dispatch_active):
        self.verify_traces.append(1)    # trace-time compile marker
        w = self.speculate_k
        act = state.active & dispatch_active
        # window w=0 is the slot's pending last_token (always correct);
        # w>=1 are the host proposer's draft tokens
        toks_in = jnp.concatenate([state.last_token[:, None], draft],
                                  axis=1)                      # (S, W)
        offs = jnp.arange(w, dtype=jnp.int32)[None, :]
        raw_pos = state.lengths[:, None] + offs
        pos = jnp.minimum(raw_pos, self.max_seq - 1)
        write_ok = act[:, None] & (raw_pos < self.max_seq)
        h, pk, pv = self.model.paged_hidden_states(
            params, toks_in, self.model_cfg, dtype=self.dtype,
            pool_k=state.pool_k, pool_v=state.pool_v,
            page_table=page_table, positions=pos, write_ok=write_ok,
            page_tokens=self.spec.page_tokens)
        g = self._greedy(params, h)                             # (S, W)
        # draft token w-1 is correct iff all earlier drafts matched the
        # target's greedy choice — cumprod counts the accepted run
        match = (draft == g[:, :-1]).astype(jnp.int32)
        a = jnp.cumprod(match, axis=1).sum(axis=1)             # (S,)
        # emit the accepted run + the target's one bonus token, clamped
        # to the generation budget and the cache capacity (>= 1 for any
        # active slot: active implies remaining > 0 and lengths <
        # max_seq)
        e = jnp.minimum(a + 1, jnp.minimum(
            state.remaining, self.max_seq - state.lengths))
        e = jnp.where(act, e, 0)
        valid = act[:, None] & (offs < e[:, None])             # (S, W)
        toks = jnp.where(valid, g, -1)
        new_last = jnp.take_along_axis(
            g, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
        new_len = jnp.where(act, state.lengths + e, state.lengths)
        new_rem = jnp.where(act, state.remaining - e, state.remaining)
        new_active = jnp.where(
            dispatch_active,
            act & (new_rem > 0) & (new_len < self.max_seq),
            state.active)
        new_state = PagedServeState(
            pool_k=pk, pool_v=pv, lengths=new_len,
            last_token=jnp.where(act, new_last, state.last_token),
            active=new_active, remaining=new_rem)
        return new_state, toks.T, valid.T, e

    def verify(self, params, state: PagedServeState, draft,
               dispatch_active=None):
        """Score a ``(slots, speculate_k - 1)`` host draft in ONE
        batched target forward (the speculative-decoding verify).
        Returns ``(state, tokens (speculate_k, slots), valid
        (speculate_k, slots), emitted (slots,))`` — the same
        ``(tokens, valid)`` orientation as :meth:`decode`, so the
        scheduler consumes both identically; ``emitted`` counts each
        slot's accepted-run + bonus tokens this dispatch. Rejected
        drafts' junk K/V lands beyond the new length and is overwritten
        (write-then-attend) before any query can reach it, which is
        what makes greedy output bitwise speculation-free."""
        params = self._resident(params)
        if self.speculate_k < 2:
            raise ValueError("verify() requires speculate_k >= 2")
        draft = jnp.asarray(draft, jnp.int32).reshape(
            self.slots, self.speculate_k - 1)
        table = jnp.asarray(self.alloc.table, jnp.int32)
        if dispatch_active is None:
            da = jnp.ones((self.slots,), bool)
        else:
            da = jnp.asarray(dispatch_active, bool).reshape(self.slots)
        args = (params, state, draft, table, da)
        self._note_program("verify", self._verify, args)
        return self._verify(*args)

    # ---------------------------------------------------------- warmup

    def warmup(self, params) -> None:
        """Compile every program OFF the request clock: a cold first
        admission would charge XLA compilation to that request's TTFT,
        and a cold ladder rung would charge a recompile to the very
        overload the downshift is trying to relieve. Runs a dummy
        prefill, one decode superstep PER LADDER RUNG and, when
        speculating, one verify, on a throwaway state and a junk page
        table (compilation only sees shapes; the junk writes route to
        the trash page), fences, and leaves the jit caches warm — after
        this, a whole serve run (adapt transitions included) compiles
        nothing (``assert_two_programs``)."""
        params = self._resident(params)
        state = self.init_state()
        dummy = jnp.zeros((1, self.prompt_pad), jnp.int32)
        row = np.full((self.spec.max_pages_per_slot,), -1, np.int32)
        state, first = self.prefill(params, state, dummy, 1, 0, 2,
                                    page_row=row)
        jax.device_get(first)
        for k in self.ladder:
            state, toks, valid = self.decode(params, state, k)
            jax.device_get((toks, valid))
        if self.speculate_k >= 2:
            draft = np.zeros((self.slots, self.speculate_k - 1),
                             np.int32)
            state, toks, valid, e = self.verify(params, state, draft)
            jax.device_get((toks, valid, e))

    def compile_counts(self) -> Tuple[int, int]:
        return len(self.prefill_traces), len(self.decode_traces)

    def assert_two_programs(self) -> None:
        """The compiled-program pin: one prefill + one decode trace PER
        LADDER RUNG for the whole run, warmup included — exactly two
        programs on the default single-rung ladder — plus exactly one
        verify program when speculation is on, and never a trace the
        warmup didn't already pay."""
        p, d = self.compile_counts()
        want = (1, len(self.ladder))
        if (p, d) != want:
            raise AssertionError(
                f"serve engine compiled {p} prefill / {d} decode "
                f"program(s), expected {want[0]}/{want[1]} for ladder "
                f"{self.ladder}; the two-program contract is broken")
        want = 1 if self.speculate_k >= 2 else 0
        v = len(self.verify_traces)
        if v != want:
            raise AssertionError(
                f"paged serve engine compiled {v} verify program(s), "
                f"expected {want} (speculate_k={self.speculate_k}); "
                f"the program-budget pin is broken")
