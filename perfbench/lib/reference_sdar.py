"""The plain reference of the ``sdar_moe`` configuration: the forward pass
in straightforward ``jax.numpy`` float32 at ``highest`` matmul precision,
under an ARBITRARY visibility mask. No kernels, no cache, no grouped
products, no batching. It imports nothing of the program and takes nothing
the program made: weights are drawn here from the seed, one layer's at a
time (seven layers are 19.9 GB in float32), rounded once to bfloat16 as the
configuration states.

The layer (``B`` = ``block_length``, ``M`` = ``mask_token_id``)::

    a  = x / sqrt(mean(x^2) + eps)                            # gains are one
    q  = a Wq -> h heads x hd;  k = a Wk, v = a Wv -> kv heads x hd
    q, k = each head / sqrt(mean(head^2) + eps)               # q/k norm
    q, k rotated, theta, whole head, pairs (i, i + hd/2), at the token's position
    A  = concat_h softmax(q_h k_g(h)^T / sqrt(hd) over the keys the mask shows) v_g(h)  Wo
    x  = x + A
    m  = x / sqrt(mean(x^2) + eps)
    r  = softmax(m Wr) over all the experts;  T = the k largest
    w_e = r_e / sum_{j in T} r_j
    x  = x + sum_{e in T} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = (x_L / sqrt(mean(x_L^2) + eps)) W_head            # untied

The logits at position i score the token AT position i. The model's own
mask is block-causal: key j is visible to query i iff j // B <= i // B.

Generation is checked by ONE forward a denoising step over a whole request
(:func:`denoise_rows`): the clean sequence followed by its noisy copy as it
stood at step ``s`` (positions unmasked at step ``s`` or later hold ``M``);
a clean position sees block-causally, a noisy position of block ``b`` sees
the clean blocks before ``b`` and the noisy block ``b`` (SDAR's training
mask). The noisy rows of that forward are what the program's denoising
forward of step ``s`` computed for every block at once, against a cache of
committed (clean) blocks.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8_e4m3fn under a per-tensor scale. ``fault`` plants one of
``FAULTS`` in the mathematics, or one of ``ORDER_FAULTS`` in the ORDER of
unmasking (the reference's own numbers, another position chosen: every
token is still the reference's first choice, so only the confidence gap can
see it): what a comparison has to catch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib.reference import _Frozen, _quant, make_room  # noqa: F401

Q_BLOCK = 128
ROW_CHUNK = 256
LEAVES = ("wq", "wk", "wv", "wo", "w_router", "e_gate", "e_up", "e_down")
FAULTS = ("causal_in_block", "commit_skipped", "qk_norm_dropped",
          "topk_unnormalised", "logits_shifted")
ORDER_FAULTS = ("unmask_left_to_right", "unmask_least_confident")
HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "dff": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "B": cfg["block_length"], "M": cfg["mask_token_id"],
            "eps": cfg["rms_norm_eps"]}


def _draw(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / jnp.sqrt(fan_in))
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def embed_weights(seed: int, cfg: dict):
    s = dims(cfg)
    return _draw(jax.random.fold_in(jax.random.PRNGKey(seed), 0),
                 (s["V"], s["d"]), s["d"])


def head_weights(seed: int, cfg: dict):
    s = dims(cfg)
    return _draw(jax.random.fold_in(jax.random.PRNGKey(seed), 1 + s["L"]),
                 (s["d"], s["V"]), s["d"])


def layer_weights(key, cfg: dict, l: int) -> dict:
    """Layer ``l``'s weights from ``key = PRNGKey(seed)``."""
    s = dims(cfg)
    d, hd, dff = s["d"], s["hd"], s["dff"]
    k = dict(zip(LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + l), len(LEAVES))))

    def stack(key, shape, fan_in):
        return jax.lax.map(
            lambda i: _draw(jax.random.fold_in(key, i), shape, fan_in),
            jnp.arange(s["E"]))
    return {
        "wq": _draw(k["wq"], (d, s["h"] * hd), d),
        "wk": _draw(k["wk"], (d, s["kv"] * hd), d),
        "wv": _draw(k["wv"], (d, s["kv"] * hd), d),
        "wo": _draw(k["wo"], (s["h"] * hd, d), s["h"] * hd),
        "w_router": _draw(k["w_router"], (d, s["E"]), d),
        "e_gate": stack(k["e_gate"], (d, dff), d),
        "e_up": stack(k["e_up"], (d, dff), d),
        "e_down": stack(k["e_down"], (dff, d), dff),
    }


def _mm(a, b, mode):
    return jnp.matmul(_quant(a, mode), _quant(b, mode), precision=HI)


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope_half(x, positions, theta):
    """x: (n, heads, hd) at ``positions`` (n,); channel i rotates with
    channel i + hd/2."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = jnp.outer(positions.astype(jnp.float32), inv)
    c, s = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend(q, k, v, mask, mode):
    """q: (n, h, hd); k, v: (n, kv, hd); mask: (n, n) bool, row i the keys
    query i sees -> (n, h, hd). In blocks of query rows."""
    n, h, hd = q.shape
    kv = k.shape[1]
    pad = -n % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, kv, h // kv, hd)
    mb = jnp.pad(mask, ((0, pad), (0, 0)), constant_values=True).reshape(
        -1, Q_BLOCK, n)

    def block(args):
        qi, see = args
        sc = jnp.einsum("qkgd,nkd->kgqn", _quant(qi, mode), _quant(k, mode),
                        precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(see[None, None], sc, -1e30), axis=-1)
        return jnp.einsum("kgqn,nkd->qkgd", _quant(p, mode), _quant(v, mode),
                          precision=HI)
    return jax.lax.map(block, (qb, mb)).reshape(-1, h, hd)[:n]


def _expert(y, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(y, wg, mode)) * _mm(y, wu, mode), wd, mode)


def _routed(y, w, cfg, mode, fault):
    """The routed sum. y: (n, d). One expert after another, each over the
    rows routed to it, gathered into a fixed number of rows (every row
    where an expert is routed more than that: exact whatever the skew)."""
    s = dims(cfg)
    n = y.shape[0]
    r = jax.nn.softmax(_mm(y, w["w_router"], mode), axis=-1)
    top_r, top_e = jax.lax.top_k(r, s["k"])
    top_w = top_r if fault == "topk_unnormalised" \
        else top_r / jnp.sum(top_r, axis=-1, keepdims=True)
    rows = n if n <= 512 else n // 4

    def one(out, ex):
        i, wg, wu, wd = ex
        w_i = jnp.sum(jnp.where(top_e == i, top_w, 0.0), axis=-1)   # (n,)

        def everywhere(_):
            return _expert(y, wg, wu, wd, mode) * w_i[:, None]

        def gathered(_):
            at = jnp.nonzero(w_i > 0, size=rows, fill_value=0)[0]
            part = _expert(y[at], wg, wu, wd, mode) * w_i[at][:, None]
            # the fill rows repeat row 0 with its own weight: drop them
            mine = jnp.arange(rows) < jnp.sum(w_i > 0)
            return jnp.zeros_like(y).at[at].add(
                jnp.where(mine[:, None], part, 0.0))
        if rows == n:
            return out + everywhere(None), None
        return out + jax.lax.cond(jnp.sum(w_i > 0) > rows, everywhere,
                                  gathered, None), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(s["E"]), w["e_gate"], w["e_up"], w["e_down"]))
    return out


def _layer(x, w, cfg, positions, mask, mode, fault):
    """One layer on ONE sequence. x: (n, d)."""
    s = dims(cfg)
    n = x.shape[0]
    a = _rms(x, s["eps"])
    q = _mm(a, w["wq"], mode).reshape(n, s["h"], s["hd"])
    k = _mm(a, w["wk"], mode).reshape(n, s["kv"], s["hd"])
    v = _mm(a, w["wv"], mode).reshape(n, s["kv"], s["hd"])
    if fault != "qk_norm_dropped":
        q, k = _rms(q, s["eps"]), _rms(k, s["eps"])
    q = _rope_half(q, positions, cfg["rope_theta"])
    k = _rope_half(k, positions, cfg["rope_theta"])
    x = x + _mm(_attend(q, k, v, mask, mode).reshape(n, -1), w["wo"], mode)
    return x + _routed(_rms(x, s["eps"]), w, cfg, mode, fault)


_layer_jit = jax.jit(_layer, static_argnums=(2, 5, 6))
_weights_jit = jax.jit(layer_weights, static_argnums=(1, 2))


def _frozen(cfg: dict) -> _Frozen:
    return _Frozen({k: (tuple(v) if isinstance(v, list) else v)
                    for k, v in cfg.items() if not isinstance(v, dict)})


def block_causal(n: int, block: int):
    """(n, n) bool: key j visible to query i iff j // block <= i // block."""
    b = jnp.arange(n) // block
    return b[None, :] <= b[:, None]


def hiddens(seed: int, cfg: dict, rows, variants=((None, None),)):
    """Final-normed hidden states. ``rows[v]``: for variant ``v`` a list of
    ``(tokens (n,), positions (n,), mask (n, n))``; -> [variant][row] ->
    (n, d). One layer's weights are alive at a time, shared by all."""
    fz = _frozen(cfg)
    eps = dims(cfg)["eps"]
    embed = embed_weights(seed, cfg)
    xs = [[embed[jnp.asarray(t)] for t, _, _ in per] for per in rows]
    del embed
    for l in range(cfg["num_hidden_layers"]):
        w = _weights_jit(jax.random.PRNGKey(seed), fz, l)
        xs = [[_layer_jit(x, w, fz, jnp.asarray(pos), mask, mode, fault)
               for x, (_, pos, mask) in zip(per_x, per)]
              for per_x, per, (mode, fault) in zip(xs, rows, variants)]
        del w
    return [[_rms(x, eps) for x in per] for per in xs]


def logits(seed: int, cfg: dict, tokens, positions=None, mask=None,
           mode=None, fault=None):
    """(n,) -> (n, vocab) float32 under ``mask`` (the model's block-causal
    one if none is given) at ``positions`` (0..n-1 if none)."""
    n = len(tokens)
    if positions is None:
        positions = np.arange(n)
    if mask is None:
        mask = block_causal(n, dims(cfg)["B"])
    h = hiddens(seed, cfg, [[(tokens, positions, mask)]],
                ((mode, fault),))[0][0]
    return _mm(h, head_weights(seed, cfg), mode)


# --------------------------------------------------------- the denoising


def request_of(prompt, served, steps, surplus, block: int) -> dict:
    """One served request as the reference needs it: every position the
    program computed, in order (the served tokens, then what the last
    block computed past the budget), the step at which each was unmasked
    (-1: the prompt's), and which were served."""
    prompt = np.asarray(prompt, np.int32)
    extra = sorted(surplus)                 # (position in block, token, step)
    tokens = np.concatenate([prompt, np.asarray(served, np.int32),
                             np.asarray([t for _, t, _ in extra], np.int32)])
    step = np.concatenate([np.full(len(prompt), -1), np.asarray(steps),
                           np.asarray([s for _, _, s in extra])]).astype(int)
    if len(tokens) % block:
        raise ValueError(f"{len(tokens)} positions are no whole number of "
                         f"blocks of {block}: the last block's surplus is "
                         f"missing")
    served_at = np.zeros(len(tokens), bool)
    served_at[len(prompt):len(prompt) + len(served)] = True
    return {"tokens": tokens, "step": step, "served": served_at}


def denoise_mask(n: int, block: int, fault=None):
    """(2n, 2n) bool over [clean (n); noisy (n)]: a clean position sees the
    clean positions block-causally; a noisy position of block b sees the
    clean blocks before b and the noisy block b. ``causal_in_block``: the
    keys ahead inside a block hidden (plain causal)."""
    i = jnp.arange(n)
    b = i // block
    inside = (i[None, :] <= i[:, None]) if fault == "causal_in_block" \
        else jnp.ones((n, n), bool)
    same = (b[None, :] == b[:, None]) & inside
    before = b[None, :] < b[:, None]
    none = jnp.zeros((n, n), bool)
    return jnp.block([[before | same, none], [before, same]])


def denoise_rows(req: dict, s: int, cfg: dict, pad_to: int, fault=None):
    """The ``[clean; noisy]`` input of denoising step ``s`` of every block
    of one request: (tokens (2 pad_to,), positions). ``commit_skipped``:
    the clean copy holds what the LAST denoising forward saw, the mask
    token at the position it was about to unmask."""
    d = dims(cfg)
    n = len(req["tokens"])
    clean = np.zeros(pad_to, np.int32)
    clean[:n] = req["tokens"]
    noisy = clean.copy()
    noisy[:n][req["step"] >= s] = d["M"]
    if fault == "commit_skipped":
        clean[:n][req["step"] == d["B"] - 1] = d["M"]
    pos = np.arange(pad_to)
    return np.concatenate([clean, noisy]), np.concatenate([pos, pos])


def _score_rows(h_ref, h_var, head, own, mode):
    """Per row: the reference's best logit, log-sum-exp and logit of the
    row's own token; the variant's probability of its first choice, and how
    far the reference's logit of THAT choice lies below its best."""
    def chunk(args):
        hr, hv, tok = args
        lg = jnp.matmul(hr, head, precision=HI)
        best = jnp.max(lg, axis=-1)
        lse = jax.nn.logsumexp(lg, axis=-1)
        mine = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        low = _mm(hv, head, mode)
        pick = jnp.argmax(low, axis=-1)
        conf = jnp.exp(jnp.max(low, axis=-1)
                       - jax.nn.logsumexp(low, axis=-1))
        gap = best - jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return best, lse, mine, conf, gap
    n, d = h_ref.shape
    c = lambda x: x.reshape((n // ROW_CHUNK, ROW_CHUNK) + x.shape[1:])
    out = jax.lax.map(chunk, (c(h_ref), c(h_var), c(own)))
    return tuple(o.reshape(n) for o in out)


_score_jit = jax.jit(_score_rows, static_argnums=(4,))


def _events(req, block):
    """[(step, the served position unmasked at it, the block's positions
    still masked before it)] of one request."""
    out = []
    for i in np.flatnonzero(req["served"]):
        s = req["step"][i]
        blk = np.arange(i // block * block, i // block * block + block)
        out.append((s, i, blk[req["step"][blk] >= s]))
    return out


def served_gaps(seed: int, cfg: dict, sample, pad_to: int,
                variants=()) -> dict:
    """``sample``: :func:`request_of` of each sampled request. ``pad_to``:
    the length each copy is padded to (a multiple of ``ROW_CHUNK`` and of
    the block; one compiled shape). For every served token, at the step it
    was unmasked: ``gaps``, how far its reference logit lies below the
    reference's best at its position (>= 0), and ``conf_gaps``, how far
    the reference's confidence at the position the program chose lies
    below its highest among the block's positions still masked (>= 0).
    For each ``(quant, fault)`` of ``variants``, under its name, the same
    two of what THAT computation would have served: the token it puts
    first, the position it would unmask. An order fault costs no forward:
    it reads the reference's own rows and chooses another position."""
    d = dims(cfg)
    order = tuple(v for v in variants if v[1] in ORDER_FAULTS)
    every = ((None, None),) + tuple(v for v in variants if v not in order)
    masks = {f: denoise_mask(pad_to, d["B"], f)
             for f in {f if f == "causal_in_block" else None
                       for _, f in every}}
    rows = []
    for mode, fault in every:
        mask = masks[fault if fault == "causal_in_block" else None]
        rows.append([denoise_rows(req, s, cfg, pad_to, fault) + (mask,)
                     for req in sample for s in range(d["B"])])
    hs = hiddens(seed, cfg, rows, every)
    del masks, rows
    head = head_weights(seed, cfg)
    out = {}
    for (mode, fault), per in list(zip(every, hs)) + [(v, hs[0])
                                                      for v in order]:
        gaps, conf_gaps = [], []
        for r, req in enumerate(sample):
            n = len(req["tokens"])
            own = np.zeros(pad_to, np.int32)
            own[:n] = req["tokens"]
            per_step = []
            for s in range(d["B"]):
                h_ref = hs[0][r * d["B"] + s][pad_to:]
                h_var = per[r * d["B"] + s][pad_to:]
                if fault == "logits_shifted":
                    # the logits read one position early
                    h_var = jnp.roll(h_var, 1, axis=0)
                best, lse, mine, conf, gap = (np.asarray(a) for a in
                                              _score_jit(h_ref, h_var, head,
                                                         jnp.asarray(own),
                                                         mode))
                per_step.append({"conf_ref": np.exp(best - lse),
                                 "gap_own": best - mine, "conf": conf,
                                 "gap": gap})
            for s, i, masked in _events(req, d["B"]):
                at = per_step[s]
                top = at["conf_ref"][masked].max()
                if mode is None and fault is None:
                    gaps.append(at["gap_own"][i])
                    conf_gaps.append(top - at["conf_ref"][i])
                else:
                    gaps.append(at["gap"][i])
                    chosen = masked[{
                        "unmask_left_to_right": 0,
                        "unmask_least_confident": np.argmin(
                            at["conf"][masked]),
                    }.get(fault, np.argmax(at["conf"][masked]))]
                    conf_gaps.append(top - at["conf_ref"][chosen])
        name = "" if (mode is None and fault is None) else (fault or mode)
        out[name + ("_" if name else "") + "gaps"] = np.asarray(gaps)
        out[name + ("_" if name else "") + "conf_gaps"] = np.asarray(
            conf_gaps)
    return out
