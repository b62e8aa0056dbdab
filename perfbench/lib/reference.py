"""The plain reference: the decoder's forward pass, its loss, gradients and
Adam in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision. No kernels, no cache, no batching tricks. It imports nothing of
the program and takes nothing the program made: weights are made here from
the seed (the same draws the program's ``models/transformer.init`` makes:
eight keys split from ``PRNGKey(seed)``, normal / sqrt(fan_in)).

It follows the published architecture (pre-norm RMSNorm, rotary embedding
on the half-split convention, grouped-query attention, SwiGLU) and follows
the program on its two departures from the published models, because the
benchmark may not change the program: the output head is tied to the
embedding, and the RMSNorm epsilon is 1e-6.

``quant="fp8"`` is the control: the same mathematics with both operands of
every matrix product rounded to float8_e4m3fn under a per-tensor scale
(the nearest precision below the bfloat16 the configurations state).

Memory: attention runs one sequence at a time and in blocks of query rows,
each block rematerialised in the backward pass, so that the float32 scores
fit beside the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6              # the program's rmsnorm epsilon (departure 2)
Q_BLOCK = 1024


def init_params(seed: int, cfg: dict) -> dict:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    dff, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    def w(key, *shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / jnp.sqrt(fan_in)))
    return {
        "embed": w(keys[0], V, d, fan_in=d),
        "layers": {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": w(keys[1], L, d, h * hd, fan_in=d),
            "wk": w(keys[2], L, d, kv * hd, fan_in=d),
            "wv": w(keys[3], L, d, kv * hd, fan_in=d),
            "wo": w(keys[4], L, h * hd, d, fan_in=h * hd),
            "ffn_norm": jnp.ones((L, d), jnp.float32),
            "w_gate": w(keys[5], L, d, dff, fan_in=d),
            "w_up": w(keys[6], L, d, dff, fan_in=d),
            "w_down": w(keys[7], L, dff, d, fan_in=dff),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }


def _quant(x, mode):
    """Round a matmul operand to the control's precision. Straight-through:
    the backward pass sees the rounded values but is itself in float32 (a
    cotangent cast to fp8 would flush to zero and freeze the parameters,
    which is the 'state unchanged' fault and not a precision)."""
    if mode is None:
        return x
    if mode != "fp8":
        raise ValueError(mode)
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, mode):
    return jnp.matmul(_quant(a, mode), _quant(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * g


def _rope(x, theta):
    """x: (seq, heads, hd), positions 0..seq-1; channel i rotates with
    channel i + hd/2."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    c, sn = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attend_block(qb, k, v, q0, mode):
    """qb: (bq, h, hd) query rows q0..q0+bq; k, v: (s, h, hd)."""
    hd = qb.shape[-1]
    sc = jnp.einsum("qhd,khd->hqk", _quant(qb, mode), _quant(k, mode),
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    qpos = q0 + jnp.arange(qb.shape[0])[:, None]
    sc = jnp.where(jnp.arange(k.shape[0])[None, :] <= qpos, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("hqk,khd->qhd", _quant(p, mode), _quant(v, mode),
                      precision=jax.lax.Precision.HIGHEST)


def _layer(x, lp, cfg, mode):
    """One decoder layer on ONE sequence. x: (seq, d)."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    s = x.shape[0]
    y = _rms(x, lp["attn_norm"])
    q = _rope(_mm(y, lp["wq"], mode).reshape(s, h, hd), cfg["rope_theta"])
    k = _rope(_mm(y, lp["wk"], mode).reshape(s, kv, hd), cfg["rope_theta"])
    v = _mm(y, lp["wv"], mode).reshape(s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    blk = jax.checkpoint(_attend_block, static_argnums=(3, 4))
    o = jnp.concatenate([blk(q[i:i + Q_BLOCK], k, v, i, mode)
                         for i in range(0, s, Q_BLOCK)], axis=0)
    x = x + _mm(o.reshape(s, h * hd), lp["wo"], mode)
    y = _rms(x, lp["ffn_norm"])
    g = jax.nn.silu(_mm(y, lp["w_gate"], mode)) * _mm(y, lp["w_up"], mode)
    return x + _mm(g, lp["w_down"], mode)


def hidden(params, tokens, cfg, mode=None):
    """tokens: (seq,) -> final-normed hidden states (seq, d)."""
    layer = jax.checkpoint(lambda x, lp: (_layer(x, lp, cfg, mode), None))
    x, _ = jax.lax.scan(layer, params["embed"][tokens], params["layers"])
    return _rms(x, params["final_norm"])


def logits(params, tokens, cfg, mode=None):
    """(seq,) -> (seq, vocab) float32, tied head."""
    return _mm(hidden(params, tokens, cfg, mode), params["embed"].T, mode)


def seq_loss(params, row, cfg, mode=None):
    """Summed next-token cross-entropy of one row of seq+1 tokens."""
    lg = logits(params, row[:-1], cfg, mode)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def batch_loss_and_grad(params, batch, cfg, mode=None, rows=None):
    """Mean loss over the tokens of ``batch`` (b, seq+1) and its gradient,
    accumulated one row at a time. ``rows`` restricts the mean to those
    rows (the 'half of the batch left out' fault)."""
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    n_tok = len(rows) * (batch.shape[1] - 1)
    vg = jax.jit(jax.value_and_grad(seq_loss), static_argnums=(2, 3))
    loss, grads = 0.0, None
    for r in rows:
        l, g = vg(params, batch[r], _Frozen(cfg), mode)
        loss = loss + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return loss / n_tok, jax.tree.map(lambda g: g / n_tok, grads)


class _Frozen(dict):
    """A hashable dict, so a configuration can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": z, "t": 0}


@jax.jit
def _adam_leaf(p, g, m, v, t, lr):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return p - lr * mh / (jnp.sqrt(vh) + eps), m, v


def adam_step(params, grads, opt, lr):
    t = opt["t"] + 1
    out = jax.tree.map(lambda p, g, m, v: _adam_leaf(p, g, m, v,
                                                     jnp.float32(t), lr),
                       params, grads, opt["m"], opt["v"])
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "t": t}


def make_room():
    """Drop every compiled program (a loaded executable holds its scratch)
    and collect what the caller has released, so that the reference fits
    where the program was."""
    import gc
    gc.collect()
    jax.clear_caches()
    gc.collect()


def delta_norms(after, before) -> dict:
    """{path: L2 norm of (after - before)} on the host, leaf by leaf."""
    out = {}
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(after)[0],
            jax.tree_util.tree_flatten_with_path(before)[0]):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        out[jax.tree_util.keystr(path)] = float(
            np.sqrt(np.sum(np.square(d, dtype=np.float64))))
    return out


def leaf_norms(tree) -> dict:
    """{path: L2 norm} with numpy on the host, leaf by leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf, dtype=np.float32)
        out[jax.tree_util.keystr(path)] = float(
            np.sqrt(np.sum(np.square(a, dtype=np.float64))))
    return out


def train_steps(seed: int, cfg: dict, batches, lr: float, mode=None,
                rows=None, frozen: bool = False) -> dict:
    """Follow the first ``len(batches)`` steps from the seed. Returns each
    step's loss, the per-leaf norm of the first gradient and the per-leaf
    norm of the parameters' change after the last step. ``frozen`` plants
    the fault 'a step that returns its state unchanged'."""
    params = init_params(seed, cfg)
    p0 = jax.tree.map(lambda a: np.asarray(a), params)
    opt = adam_init(params)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        loss, grads = batch_loss_and_grad(params, jnp.asarray(b), cfg, mode,
                                          rows)
        losses.append(float(loss))
        if i == 0:
            g1 = leaf_norms(grads)
        if not frozen:
            params, opt = adam_step(params, grads, opt, lr)
        del grads
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": delta_norms(params, p0)}


def served_gaps(params, prompt, served, cfg, pad_to: int, mode=None) -> dict:
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal:
    the padding cannot reach an earlier position; one compiled shape). For
    each served token: how far its reference logit lies below the
    reference's best at that position (>= 0). With ``mode`` set, also the
    gap of the token the lower precision puts first at each of those
    positions (the control)."""
    n, m = len(prompt), len(served)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n + m - 1] = np.concatenate([prompt, served[:-1]])
    fwd = jax.jit(logits, static_argnums=(2, 3))
    ref = fwd(params, jnp.asarray(toks), _Frozen(cfg), None)[n - 1:n - 1 + m]
    best = jnp.max(ref, axis=-1)
    tok = jnp.asarray(np.asarray(served, np.int32))
    gap = best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
    out = {"gaps": np.asarray(gap)}
    if mode is not None:
        low = fwd(params, jnp.asarray(toks), _Frozen(cfg),
                  mode)[n - 1:n - 1 + m]
        pick = jnp.argmax(low, axis=-1)
        out["control_gaps"] = np.asarray(
            best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0])
    return out
