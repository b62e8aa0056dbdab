"""Sharded KV cache for the serving engine: a paged pool.

K and V live in fixed-size pages of ``page_tokens`` positions in a pool
of ``pages`` (+1 sacrificial TRASH page), mapped to slots through a
host-owned slot→page table (:class:`PagedCacheSpec` +
:class:`PageAllocator`, vLLM-style). A slot only holds pages for
positions it has actually written, so the pool can be sized well below
``slots × max_seq`` — the freed HBM becomes sustained concurrency.
Full prefix pages of a common system prompt are REFCOUNTED and shared
across every slot (``register_shared``); the partial tail page is
"forked" copy-on-write at admission (the prefill recomputes those
positions into the slot's first private page — bitwise-identical
content, same tokens at the same absolute positions), so no slot ever
writes a shared page. Invalid/masked writes are routed to the trash
page (pool index ``pages``), which no page table ever references and
the ownership mask therefore never reads.

The page table itself never lives on device state: the HOST allocator
owns it and each dispatch passes the current table in as a small traced
int32 array — the compiled programs stay exactly the programs the
two-program discipline pinned (tpudist.serve.engine), and admission /
eviction / page exhaustion are pure host decisions between dispatches.

GQA-aware by construction: the pool stores the COMPACT kv heads (the
same layout the models' ``wk``/``wv`` produce) and expansion to the
query head count happens inside the attention math — an 8×-grouped
model's cache is 8× smaller than a naive full-head cache, which is the
difference between fitting long contexts in HBM or not.

A model with LATENT attention (``ModelConfig.kv_lora_rank``) keeps a third
kind of row: ONE row a token an attention sublayer, ``[latent | rope key]``
up to whole lanes (``ModelConfig.latent_row``: 576 values stored at 640),
with no kv-head axis to speak of and no V pool: the values are the row's
own first lanes. The pool is then ``(sublayers, 1, pages+1, page_tokens,
latent_row)`` and ``init_paged_cache`` makes ``"v": None``. 1,280 B a token
a sublayer in bfloat16 where 64 heads of 128 as K and V rows are 32,768 B:
what lets such a model serve hundreds of sequences a chip.

Sharding rides the existing mesh machinery: ``parallel.sharding.
paged_kv_cache_specs`` is the ``param_specs``-style single source for
the PartitionSpec (pages over the batch axes, kv heads over tensor),
sanitised per-mesh exactly like model params.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpudist.config import ModelConfig
from tpudist.parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class PagedCacheSpec:
    """Static shape/dtype of one serving run's PAGED KV pool.

    ``pages`` is the usable pool size; the physical pool carries one
    extra sacrificial TRASH page at index ``pages`` where every
    masked/invalid write is routed (a page table never references it,
    so the ownership mask never reads it). ``page_tokens`` is the fixed
    page length in positions; ``max_pages_per_slot`` is the page-table
    row width (``ceil(max_seq / page_tokens)``).

    A model with WINDOW layers (``cfg.sliding_window``) keeps two kinds of
    state side by side. ``n_layers`` then counts the FULL layers only:
    they alone need every token of a slot and live in the pool. Each of
    the ``window_layers`` keeps, per slot, a ring of ``ring_tokens`` =
    window + ``ring_margin`` positions (the window a query reads plus
    what one dispatch may append before it reads), statically one per
    slot: a window layer's state never grows with the sequence and is
    never what an admission waits for.

    A model with LATENT attention (``cfg.kv_lora_rank``) has ``latent``
    set: ``n_layers`` counts its attention SUBLAYERS (two a layer),
    ``n_kv_heads`` is 1, ``head_dim`` the stored row's width, and there is
    one pool, not a K and a V."""

    n_layers: int
    slots: int
    max_seq: int
    n_kv_heads: int
    head_dim: int
    page_tokens: int
    pages: int
    dtype: Any = jnp.float32
    window_layers: int = 0
    ring_tokens: int = 0
    latent: bool = False

    @classmethod
    def from_model(cls, cfg: ModelConfig, *, slots: int, max_seq: int,
                   page_tokens: int, pages: int = 0, dtype=jnp.float32,
                   ring_margin: int = 128) -> "PagedCacheSpec":
        if not 0 < page_tokens <= max_seq:
            raise ValueError(
                f"--kv-page-tokens {page_tokens} must be in (0, "
                f"max_seq {max_seq}]")
        maxp = -(-max_seq // page_tokens)
        if pages <= 0:
            # default pool = every slot at max_seq: correctness-neutral
            # sizing (admission can never be denied); operators shrink
            # it to trade capacity for sustained concurrency
            pages = slots * maxp
        if cfg.kv_lora_rank:
            return cls(n_layers=2 * cfg.n_layers, slots=slots,
                       max_seq=max_seq, n_kv_heads=1,
                       head_dim=cfg.latent_row,
                       page_tokens=int(page_tokens), pages=int(pages),
                       dtype=dtype, latent=True)
        n_window = 0
        if cfg.sliding_window:
            from tpudist.models import get_model
            n_window = get_model(cfg.name).layer_kinds(cfg).count("sliding")
        return cls(n_layers=cfg.n_layers - n_window, slots=slots,
                   max_seq=max_seq, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.head_size,
                   page_tokens=int(page_tokens), pages=int(pages),
                   dtype=dtype, window_layers=n_window,
                   ring_tokens=(min(cfg.sliding_window + int(ring_margin),
                                    max_seq) if n_window else 0))

    @property
    def max_pages_per_slot(self) -> int:
        return -(-self.max_seq // self.page_tokens)

    @property
    def pool_shape(self) -> tuple:
        # +1: the trash page. kv heads OUTSIDE the pages: a layer's
        # slice is then the (kv, keys, head_dim) operand the attention
        # matmuls batch over, with no relayout on the way
        return (self.n_layers, self.n_kv_heads, self.pages + 1,
                self.page_tokens, self.head_dim)

    @property
    def ring_shape(self) -> tuple:
        """ONE window layer's ring (there are ``window_layers`` of them,
        each an array of its own: a layer reads its ring whole, and a
        slice out of a stack would be a copy of it)."""
        return (self.n_kv_heads, self.slots, self.ring_tokens,
                self.head_dim)

    @property
    def table_bytes(self) -> int:
        return self.slots * self.max_pages_per_slot * 4   # int32 table

    @property
    def window_bytes(self) -> int:
        """The window layers' rings, K and V."""
        n = self.window_layers
        for d in self.ring_shape:
            n *= d
        return 2 * n * jnp.dtype(self.dtype).itemsize

    @property
    def pools(self) -> int:
        """Pools of ``pool_shape``: K and V, or a latent cache's one."""
        return 1 if self.latent else 2

    @property
    def bytes(self) -> int:
        """The PAGED footprint: pool pages (trash included — it is
        real HBM) × page bytes for K and V (a latent cache's one pool,
        dead lanes included), plus the page-table overhead, plus the
        window layers' rings. This is the number serve_tick reports: what
        is actually allocated, not ``slots × max_seq``."""
        n = 1
        for d in self.pool_shape:
            n *= d
        return self.pools * n * jnp.dtype(self.dtype).itemsize \
            + self.table_bytes + self.window_bytes


def paged_cache_shardings(spec: PagedCacheSpec, mesh) -> Any:
    """NamedSharding for the paged K/V pools: pages ride the batch axes
    (the pool's embarrassingly-parallel dim), kv heads ride tensor —
    sanitised like model params."""
    shape = jax.ShapeDtypeStruct(spec.pool_shape, spec.dtype)
    pspec = shd.sanitize_specs(shape, shd.paged_kv_cache_specs(), mesh)
    return shd.named(mesh, pspec)


def init_paged_cache(spec: PagedCacheSpec, mesh=None
                     ) -> Dict[str, jax.Array]:
    """Zero-initialised paged ``{"k", "v"}`` pool (trash page included),
    placed to its mesh sharding when one is given. A latent cache is ONE
    pool: ``"v"`` is None."""
    k = jnp.zeros(spec.pool_shape, spec.dtype)
    v = None if spec.latent else jnp.zeros(spec.pool_shape, spec.dtype)
    if mesh is not None:
        sh = paged_cache_shardings(spec, mesh)
        k = jax.device_put(k, sh)
        v = None if v is None else jax.device_put(v, sh)
    return {"k": k, "v": v}


def init_rings(spec: PagedCacheSpec, mesh=None) -> Dict[str, tuple]:
    """Zero-initialised ``{"k", "v"}`` tuples of one ring per window
    layer (empty tuples for a model without window layers), replicated
    over ``mesh``: slots do not divide a ring the way pages divide the
    pool, and the one deployment that has rings serves one chip."""
    def one():
        r = jnp.zeros(spec.ring_shape, spec.dtype)
        return r if mesh is None else jax.device_put(r, shd.replicated(mesh))
    return {"k": tuple(one() for _ in range(spec.window_layers)),
            "v": tuple(one() for _ in range(spec.window_layers))}


class PageAllocatorError(RuntimeError):
    """An allocator invariant broke (refcount underflow, double free) —
    a HOST bug, raised loudly rather than silently corrupting the
    slot→page mapping the compiled programs trust."""


class PageAllocator:
    """Host-side page bookkeeping for one paged serve run.

    Owns the slot→page table (``table``, int32 ``(slots,
    max_pages_per_slot)``, -1 = unmapped) and the free list. Pages are
    REFCOUNTED: private pages hold refcount 1 (their slot); shared
    prefix pages hold one count per using slot PLUS one registry hold
    (``register_shared``) so the cached prefix survives every slot
    freeing. All methods are deterministic (free list is FIFO in page
    order) so a seeded serve run admits the same pages every run.
    """

    def __init__(self, spec: PagedCacheSpec):
        self.spec = spec
        self.free: List[int] = list(range(spec.pages))
        self.refcount = np.zeros((spec.pages,), np.int64)
        self.table = np.full(
            (spec.slots, spec.max_pages_per_slot), -1, np.int32)
        # shared prefix registry: logical page index -> page id, plus
        # how many leading POSITIONS those full pages cover
        self.shared_pages: Tuple[int, ...] = ()
        self.shared_len = 0
        # admission page ceiling: the whole pool by default; a memory
        # bound (set_memory_bound) lowers it when device HBM cannot
        # actually afford every configured page beside the params and
        # the compiled programs' scratch
        self.page_cap = spec.pages
        self.bound_source = "none"     # none | ledger | heuristic
        # the other kind of state: tokens each slot's rings hold (the
        # same count in every window layer). Statically one ring a slot,
        # so it bounds no admission; it is accounted so that a run can
        # say how full each kind was
        self.window_held = np.zeros((spec.slots,), np.int64)

    def set_memory_bound(self, *, hbm_bytes: float,
                         params_bytes: float = 0,
                         program_temp_bytes: Optional[int] = None
                         ) -> int:
        """Cap admissions to the pages device HBM can actually afford.

        The pool array is allocated in full either way; what this bounds
        is how many pages admission will ever MAP — so a pool configured
        past the device's real capacity backpressures at admission
        (requests wait or are structurally rejected) instead of letting
        the next allocation spike die in RESOURCE_EXHAUSTED. The margin
        reserved beside the pool is ledger-informed when a prior run's
        memory ledger measured the compiled programs' real scratch
        (``program_temp_bytes``, obs.memledger); without a ledger it
        falls back to the staging resolver's conservative
        ``STAGING_STATE_HEADROOM x params`` guess. ``bound_source``
        records which path won (the serve CLI logs it). Returns the
        resulting page cap, clamped to [0, spec.pages] — shared-prefix
        registry pages always stay admissible."""
        from tpudist.config import STAGING_STATE_HEADROOM
        if program_temp_bytes is not None and program_temp_bytes >= 0:
            margin = float(params_bytes) + float(program_temp_bytes)
            self.bound_source = "ledger"
        else:
            margin = STAGING_STATE_HEADROOM * float(params_bytes)
            self.bound_source = "heuristic"
        page_bytes = self.spec.pools * self.spec.n_layers \
            * self.spec.page_tokens \
            * self.spec.n_kv_heads * self.spec.head_dim \
            * jnp.dtype(self.spec.dtype).itemsize
        avail = float(hbm_bytes) - margin - self.spec.table_bytes \
            - self.spec.window_bytes
        cap = int(avail // page_bytes) if page_bytes > 0 else 0
        cap = max(cap, len(self.shared_pages))
        self.page_cap = min(max(cap, 0), self.spec.pages)
        return self.page_cap

    # ------------------------------------------------------- internal

    def _take(self) -> Optional[int]:
        # the memory bound caps LIVE pages, not just the free list: a
        # pool configured past what HBM affords backpressures here
        if not self.free or self.pages_used() >= self.page_cap:
            return None
        pg = self.free.pop(0)
        self.refcount[pg] += 1
        return pg

    def _drop(self, pg: int) -> None:
        if self.refcount[pg] <= 0:
            raise PageAllocatorError(
                f"page {pg} refcount underflow: freed more times than "
                f"held — the slot→page bookkeeping is corrupt")
        self.refcount[pg] -= 1
        if self.refcount[pg] == 0:
            self.free.append(pg)

    # --------------------------------------------------------- shared

    def register_shared(self, prefix_len: int) -> Tuple[int, ...]:
        """Reserve the FULL pages of a ``prefix_len``-token shared
        prefix (the partial tail page is never shared — admission forks
        it into the slot's first private page by recomputation). Each
        reserved page takes a registry hold so it survives all slots
        freeing. Returns the reserved page ids, in logical order."""
        if self.shared_pages:
            raise PageAllocatorError("shared prefix already registered")
        pt = self.spec.page_tokens
        n_full = max(int(prefix_len), 0) // pt
        pages: List[int] = []
        for _ in range(n_full):
            pg = self._take()
            if pg is None:
                for p in pages:        # rollback: nothing half-shared
                    self._drop(p)
                raise PageAllocatorError(
                    f"pool of {self.spec.pages} pages cannot hold the "
                    f"{n_full}-page shared prefix")
            pages.append(pg)
        self.shared_pages = tuple(pages)
        self.shared_len = n_full * pt
        return self.shared_pages

    # ------------------------------------------------------ lifecycle

    def admit(self, slot: int, prompt_len: int,
              shared: bool = False) -> bool:
        """Map pages for one admission: shared full prefix pages (when
        ``shared``) plus private pages covering positions
        ``[shared_len, prompt_len)``. All-or-nothing — a pool too empty
        rolls back and returns False (the request stays WAITING, it is
        not shed: admission denial by page exhaustion is backpressure,
        not overload shedding)."""
        if (self.table[slot] >= 0).any():
            raise PageAllocatorError(
                f"slot {slot} admitted while still holding pages")
        pt = self.spec.page_tokens
        need = -(-int(prompt_len) // pt)            # pages [0, need)
        row = np.full((self.spec.max_pages_per_slot,), -1, np.int32)
        taken: List[int] = []
        for j in range(need):
            if shared and j < len(self.shared_pages):
                pg = self.shared_pages[j]
                self.refcount[pg] += 1              # one hold per slot
            else:
                got = self._take()
                if got is None:
                    for p in taken:
                        self._drop(p)
                    if shared:
                        for jj in range(min(j, len(self.shared_pages))):
                            self._drop(self.shared_pages[jj])
                    return False
                pg = got
                taken.append(pg)
            row[j] = pg
        self.table[slot] = row
        self._hold_window(slot, prompt_len)
        return True

    def admit_shared_len(self, shared: bool) -> int:
        """The prefill's ``shared_len`` traced scalar for an admission:
        positions below it are NOT written (their pages are the shared
        prefix, already holding bitwise-identical content)."""
        return self.shared_len if shared else 0

    def ensure(self, slot: int, last_pos: int) -> bool:
        """Grow a live slot's mapping to cover positions up to
        ``last_pos`` (inclusive, clamped to the cache capacity) before
        a dispatch writes them. All-or-nothing like :meth:`admit`."""
        pt = self.spec.page_tokens
        upto = min(int(last_pos), self.spec.max_seq - 1) // pt
        taken: List[Tuple[int, int]] = []
        for j in range(upto + 1):
            if self.table[slot, j] >= 0:
                continue
            pg = self._take()
            if pg is None:
                for jj, p in taken:
                    self._drop(p)
                    self.table[slot, jj] = -1
                return False
            taken.append((j, pg))
            self.table[slot, j] = pg
        self._hold_window(slot, min(int(last_pos), self.spec.max_seq - 1)
                          + 1)
        return True

    def free_slot(self, slot: int) -> None:
        """Return a finished/evicted slot's pages. Shared prefix pages
        drop ONE count (the registry hold keeps them cached for the
        next admission); private pages return to the free list."""
        for j in range(self.spec.max_pages_per_slot):
            pg = int(self.table[slot, j])
            if pg >= 0:
                self._drop(pg)
            self.table[slot, j] = -1
        self.window_held[slot] = 0

    def _hold_window(self, slot: int, tokens: int) -> None:
        if self.spec.window_layers:
            self.window_held[slot] = min(int(tokens), self.spec.ring_tokens)

    # ------------------------------------------------------- queries

    def row(self, slot: int) -> np.ndarray:
        return self.table[slot].copy()

    def pages_used(self) -> int:
        """Pages of the FULL layers' pool that are mapped."""
        return self.spec.pages - len(self.free)

    def window_tokens_used(self) -> int:
        """Tokens the WINDOW layers' rings hold, over all slots (each
        window layer holds as many)."""
        return int(self.window_held.sum())

    def can_ever_admit(self, prompt_len: int, shared: bool) -> bool:
        """Could this admission EVER succeed, even with every slot
        freed? False means the request is structurally unservable at
        this pool size — or at the memory bound's ledger/heuristic page
        cap when one is set — (reject it: waiting forever would wedge
        the run); the shared-prefix registry holds are the only
        permanent reservation."""
        pt = self.spec.page_tokens
        need = -(-int(prompt_len) // pt)
        if shared:
            need = max(need - len(self.shared_pages), 0)
        usable = min(self.spec.pages, self.page_cap)
        return need <= usable - len(self.shared_pages)
