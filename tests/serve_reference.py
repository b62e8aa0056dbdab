"""The serving tests' independent reference: the uncached TRAINING-path
forward and a naive greedy loop over it. Nothing here touches the serve
engine, its programs or the KV pool."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpudist.models import get_model


def _logits(cfg, params, tokens):
    out = get_model(cfg.name).hidden_states(params, tokens, cfg,
                                            dtype=jnp.float32)
    h = out[0] if isinstance(out, tuple) else out
    return (h @ params["embed"].astype(jnp.float32).T).astype(jnp.float32)


def ref_logits(cfg, params, seq) -> np.ndarray:
    """Full-forward reference: logits (seq, vocab) f32 for one sequence
    through the TRAINING path (no cache) — the anchor the serving path
    is graded against."""
    return np.asarray(_logits(cfg, params,
                              jnp.asarray(seq, jnp.int32)[None]))[0]


@functools.lru_cache(maxsize=None)
def _padded_forward(cfg, width: int):
    # one compile a (model, width): a causal forward over a zero-padded
    # row reads the same logits at the positions before the padding
    return jax.jit(lambda params, row: _logits(cfg, params, row[None])[0])


def greedy_tokens(cfg, params, requests) -> dict:
    """``{rid: tokens}`` of a naive greedy decode: one whole forward
    over the growing sequence per generated token."""
    width = -(-max(r.prompt_len + r.max_new for r in requests) // 8) * 8
    forward = _padded_forward(cfg, width)
    out = {}
    for req in requests:
        n = req.prompt_len
        row = np.zeros((width,), np.int32)
        row[:n] = req.tokens[:n]
        want = []
        for _ in range(req.max_new):
            want.append(int(np.argmax(np.asarray(
                forward(params, jnp.asarray(row)))[n - 1])))
            row[n] = want[-1]
            n += 1
        out[req.rid] = want
    return out
