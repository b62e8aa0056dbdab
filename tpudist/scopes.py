"""The program's layers as ``jax.named_scope`` names: an interface.

A ``jax.profiler`` capture names device time by what the compiler made
(``fusion.123``, ``copy.7``); the name stack an op was traced under is the
only thing that says which LAYER it belongs to. It travels in the op's
metadata (``op_name``; the capture's ``XLA Ops`` events carry it as the
``tf_op`` argument on a v5e, jaxlib 0.9.0), so the names below are read
back by ``obs.devtime`` (``by_scope`` in the ``kind=devtime`` record, the
run report's "Device time" section) and by the benchmark's per-layer
metrics (``perfbench/metrics/*scope*.json``). Renaming one renames a
metric's source: add, do not rename.

The name stack is debug info, and jax's persistent-cache key strips debug
info by default: ``utils.platform.enable_compilation_cache`` puts it back
into the key, or a scoped program could be served an unscoped executable.

No jax import at module level: the parser half (:func:`scope_path`) runs
in the jax-free offline report.
"""

from __future__ import annotations

import functools
import re
from typing import Optional, Tuple

# Every scope the program enters, as written at the call site. A nested
# scope's path is its parents' joined by "/" (``decode/attn/kv_write``);
# backward ops carry the same names under ``transpose(jvp(loss))``.
SCOPES: Tuple[str, ...] = (
    "embed",            # token-embedding gather
    "norm",             # rmsnorm (attention, FFN and final)
    "attn/qkv",         # q/k/v projections
    "attn/rope",        # rotary embedding outside the flash kernel
    "attn/core",        # scores, softmax, values (flash kernel on TPU)
    "attn/out",         # output projection + residual
    "attn/kv_write",    # KV cache / paged pool update
    "attn/kv_gather",   # KV read-side layout: GQA expand, page ownership
    "ffn",              # SwiGLU FFN + residual
    "lm_head",          # tied output head (+ cross-entropy in training)
    "cast",             # stored weight -> compute dtype
    "loss",             # the whole loss function (forward and backward)
    "optimizer",        # optax update + apply_updates
    "prefill",          # serve: the prefill program
    "kv_scatter",       # serve, inside prefill (``prefill/kv_scatter``):
                        # the prompt's K/V scattered into its pages
    "decode",           # serve: the decode (and verify) program
    "sample",           # serve: greedy argmax
)

_WORDS = frozenset(w for s in SCOPES for w in s.split("/"))
_WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([A-Za-z0-9_.\-]+)\)*$")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES` only."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of tpudist.scopes.SCOPES")
    import jax
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the whole function traces under ``scope(name)`` (the
    outermost scope of a compiled program's body). The function keeps
    its name, so the program's module name in a capture does not move."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def cast(w, dtype):
    """A stored weight converted to the compute dtype, under the scope
    ``cast``: a conversion XLA does not fuse into its consumer shows in
    a capture under that name, not as an anonymous ``convert``."""
    with scope("cast"):
        return w.astype(dtype)


def scope_path(op_name: Optional[str]) -> str:
    """The program's scope path of one op: ``jit(superstep)/while/body/
    closed_call/transpose(jvp(loss))/attn/qkv/dot_general:`` ->
    ``loss/attn/qkv``. Segments jax adds (``jit(..)``, ``while``, ``body``,
    ``cond``, the trailing primitive) drop out; autodiff's wrappers are
    unwrapped. Empty when the op carries none of the program's scopes."""
    if not op_name:
        return ""
    kept = []
    for seg in op_name.rstrip(":").split("/")[:-1]:
        if seg.startswith(("jit(", "pjit(")):
            continue
        m = _WRAPPED.match(seg)
        if m and m.group(1) in _WORDS:
            kept.append(m.group(1))
    return "/".join(kept)


# scopes that wrap a whole program or pass: a layer's name is what comes
# under them
_WRAPPERS = ("loss", "prefill", "decode")


def layer_of(path: str) -> str:
    """The layer a scope path is booked under: ``cast`` wherever it
    appears (a weight conversion belongs to no layer's math), else the
    first segment under the program wrappers (``loss/attn/qkv`` ->
    ``attn``), else the wrapper itself (``decode``: the scan's own
    bookkeeping). Empty for an empty path."""
    segs = path.split("/") if path else []
    if "cast" in segs:
        return "cast"
    for seg in segs:
        if seg not in _WRAPPERS:
            return seg
    return segs[0] if segs else ""
