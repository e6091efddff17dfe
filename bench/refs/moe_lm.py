"""Plain float32 reference of a decoder-only language model with sparse
experts, sliding-window and full attention: its coded training step,
weighted loss, gradient and AdamW, in straight ``jax.numpy`` with no
kernels, caches, scans or grouped matmuls.

It imports nothing of the system under test; it takes the norms, the
float8 control and AdamW from ``dense_lm``.  It reads weights in the tree
layout the system under test consumes (the benchmark makes them with
``init_params`` and hands the same tree to both).  With P the length of
``block_pattern``, layer l sits at pattern position l % P of group
l // P; whole groups are stacked:

    embed [Vp, d], final_norm {scale}, head [d, Vp] (untied)
    stack/p<i>/ln1, ln2 {scale}                   leading dim = groups
    stack/p<i>/attn {wq, wk, wv, wo}
    stack/p<i>/moe  {router [d, E], wg, wi [H, d, f], wo [H, f, d]}
    rem/r<i>/...                                  the last n_layers % P

Mechanisms, as the configuration states them:

* RMSNorm ``x / rms(x) * (1 + scale)``, eps 1e-6.
* Attention: grouped-query heads (``n_heads / n_kv`` query heads share
  a key/value head), causal softmax over ``q k^T / sqrt(d_head)``; a
  layer's window (``window_pattern``) masks keys ``window`` or more
  positions back.  RoPE (rotate-half pairing, base ``rope_theta``); a
  ``yarn`` layer (``rope_pattern``) uses YaRN frequencies
  ``b^(-2i/d)/factor * ramp_i + b^(-2i/d) * (1 - ramp_i)`` with the
  ramp from floor(fcd(beta_fast)) to ceil(fcd(beta_slow)),
  fcd(r) = d ln(L0 / (2 pi r)) / (2 ln b), and cos and sin times
  ``attention_factor``.
* Experts: a softmax router over all E experts, the top K renormalised
  to sum to one; each of the H experts held here
  (``moe.held = [first, H]``) runs SwiGLU ``(silu(x wg) * (x wi)) wo``
  densely on every token, times its gate, or 0 where the token did not
  pick it.  What the other experts would add is left out.
* Loss of row r: its mean token cross-entropy over the real vocabulary,
  plus 0.01 aux_r, aux_r = E sum_e f_e p_e over the row's own tokens and
  all E experts (f_e the share of its top-K picks on e, p_e its mean
  router probability); a step's objective is the coded weighted sum of
  the rows' losses, aux included.  The loss it reports is the weighted
  sum of the CE alone, as the program's history logs it.

Every matmul runs at ``jax.default_matmul_precision("highest")``.  Each
layer is recomputed in the backward pass and attention runs one
key/value head at a time, so that a 4096-position row fits one chip.
The control (``precision="fp8"``) runs the same model with every
matmul's operands rounded to float8, as in ``dense_lm``.
"""

from __future__ import annotations

import math
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import dense_lm

padded_vocab = dense_lm.padded_vocab
leaf_norms = dense_lm.leaf_norms
lr_at = dense_lm.lr_at


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def _layer_shapes(cfg: dict, prefix: tuple) -> dict:
    d = cfg["d_model"]
    qd, kvd = cfg["n_heads"] * cfg["d_head"], cfg["n_kv"] * cfg["d_head"]
    m = cfg["moe"]
    H, f = m["held"][1], m["d_ff_expert"]
    return {"ln1": {"scale": prefix + (d,)},
            "attn": {"wq": prefix + (d, qd), "wk": prefix + (d, kvd),
                     "wv": prefix + (d, kvd), "wo": prefix + (qd, d)},
            "ln2": {"scale": prefix + (d,)},
            "moe": {"router": prefix + (d, m["num_experts"]),
                    "wg": prefix + (H, d, f), "wi": prefix + (H, d, f),
                    "wo": prefix + (H, f, d)}}


def param_shapes(cfg: dict) -> dict:
    """The weight tree's shapes, one entry per leaf."""
    P = len(cfg["block_pattern"])
    groups, rem = divmod(cfg["n_layers"], P)
    vp, d = padded_vocab(cfg), cfg["d_model"]
    tree = {"embed": (vp, d), "final_norm": {"scale": (d,)},
            "head": (d, vp),
            "rem": {f"r{i}": _layer_shapes(cfg, ()) for i in range(rem)}}
    if groups:
        tree["stack"] = {f"p{i}": _layer_shapes(cfg, (groups,))
                         for i in range(P)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Weights from a key, float32: matrices normal with std
    1/sqrt(fan-in), the embedding std 0.02, RMSNorm offsets zero.
    Deterministic in the key; jit it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, shape), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if name == "['embed']":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        elif name.endswith("['scale']"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out.append(std * jax.random.normal(k, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def layer_params(params, cfg: dict, layer: int) -> dict:
    """Layer ``layer``'s weights, without the stacking."""
    P = len(cfg["block_pattern"])
    g, i = divmod(layer, P)
    if g < cfg["n_layers"] // P:
        return jax.tree_util.tree_map(lambda t: t[g],
                                      params["stack"][f"p{i}"])
    return params["rem"][f"r{i}"]


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def rope_tables(cfg: dict, pos: int, S: int):
    """cos, sin [S, d_head / 2] of pattern position ``pos`` (float64)."""
    dh, theta = cfg["d_head"], cfg["rope_theta"]
    half = dh // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    scale = 1.0
    kinds = cfg.get("rope_pattern") or []
    if kinds and kinds[pos] == "yarn":
        y = cfg["yarn"]

        def fcd(r):
            return dh * math.log(y["original_max_position_embeddings"]
                                 / (2 * math.pi * r)) / (2 * math.log(theta))
        lo = max(math.floor(fcd(y["beta_fast"])), 0)
        hi = min(math.ceil(fcd(y["beta_slow"])), dh - 1)
        ramp = np.clip((np.arange(half) - lo) / (hi - lo), 0.0, 1.0)
        freq = freq / y["factor"] * ramp + freq * (1.0 - ramp)
        scale = y["attention_factor"]
    ang = np.arange(S, dtype=np.float64)[:, None] * freq[None, :]
    return np.cos(ang) * scale, np.sin(ang) * scale


def _rope(x, cos, sin):
    """x [B, S, H, dh]: rotate pairs (i, i + dh/2)."""
    half = x.shape[-1] // 2
    cos = jnp.asarray(cos, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(sin, jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _window(cfg: dict, pos: int) -> int:
    windows = cfg.get("window_pattern") or []
    return windows[pos] if windows else cfg.get("local_window", 0)


def _attention(p, h, cfg, pos, es):
    B, S, _ = h.shape
    H, Kv, dh = cfg["n_heads"], cfg["n_kv"], cfg["d_head"]
    G = H // Kv
    cos, sin = rope_tables(cfg, pos, S)
    q = _rope(es("bsd,de->bse", h, p["wq"]).reshape(B, S, H, dh), cos, sin)
    k = _rope(es("bsd,de->bse", h, p["wk"]).reshape(B, S, Kv, dh), cos, sin)
    v = es("bsd,de->bse", h, p["wv"]).reshape(B, S, Kv, dh)
    qi, ki = np.arange(S)[:, None], np.arange(S)[None, :]
    allowed = qi >= ki
    window = _window(cfg, pos)
    if window:
        allowed &= qi - ki < window
    outs = []
    for n in range(Kv):                # query heads n G .. n G + G - 1
        s = es("bqgd,bkd->bgqk", q[:, :, n * G:(n + 1) * G], k[:, :, n])
        s = jnp.where(allowed, s / math.sqrt(dh), -jnp.inf)
        outs.append(es("bgqk,bkd->bqgd", jax.nn.softmax(s, axis=-1),
                       v[:, :, n]))
    att = jnp.concatenate(outs, axis=2).reshape(B, S, H * dh)
    return es("bse,ed->bsd", att, p["wo"])


def _experts(p, h, cfg, es):
    """(the held experts' part of the layer [B, S, d], aux [B])."""
    m = cfg["moe"]
    E, K = m["num_experts"], m["top_k"]
    first, H = m["held"]
    S = h.shape[1]
    probs = jax.nn.softmax(es("bsd,de->bse", h, p["router"]), axis=-1)
    top, ids = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(ids, E, dtype=jnp.float32)       # [B, S, K, E]
    gates = jnp.sum(picked * top[..., None], axis=-2)        # [B, S, E]
    f = jnp.sum(picked, axis=(1, 2)) / (S * K)               # [B, E]
    aux = E * jnp.sum(f * jnp.mean(probs, axis=1), axis=-1)
    out = jnp.zeros_like(h)
    for j in range(H):
        g = jax.nn.silu(es("bsd,df->bsf", h, p["wg"][j]))
        u = es("bsd,df->bsf", h, p["wi"][j])
        out = out + gates[..., first + j, None] * es("bsf,fd->bsd", g * u,
                                                     p["wo"][j])
    return out, aux


def row_losses(params, cfg: dict, tokens, labels, precision: str = "fp32"):
    """[B] mean token cross-entropy and [B] aux loss of each row."""
    es = dense_lm._einsum_fn(precision)
    V, P = cfg["vocab"], len(cfg["block_pattern"])
    norm = cfg["norm"]

    def layer(p, x, pos):
        x = x + _attention(p["attn"], dense_lm._norm(x, p["ln1"], norm),
                           cfg, pos, es)
        ff, aux = _experts(p["moe"], dense_lm._norm(x, p["ln2"], norm), cfg,
                           es)
        return x + ff, aux

    x = params["embed"][tokens]
    aux = jnp.zeros(tokens.shape[:1], jnp.float32)
    for i in range(cfg["n_layers"]):
        x, a = jax.checkpoint(layer, static_argnums=(2,))(
            layer_params(params, cfg, i), x, i % P)
        aux = aux + a
    x = dense_lm._norm(x, params["final_norm"], norm)
    logits = es("bsd,dv->bsv", x, params["head"][:, :V])
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - lab, axis=-1), aux


# --------------------------------------------------------------------------
# the coded training step
# --------------------------------------------------------------------------


def make_block_grad(cfg: dict, precision: str = "fp32") -> Callable:
    """jitted (params, acc, tokens [R, S], labels [R, S], coeff []) ->
    (coeff * sum of the rows' CE, acc + the gradient of coeff * sum of
    CE + 0.01 aux): one task's rows, added into the donated accumulator
    ``acc``."""
    def loss(params, tokens, labels, coeff):
        with jax.default_matmul_precision("highest"):
            ce, aux = row_losses(params, cfg, tokens, labels, precision)
            return coeff * jnp.sum(ce + 0.01 * aux), coeff * jnp.sum(ce)

    def block(params, acc, tokens, labels, coeff):
        (_, value), grad = jax.value_and_grad(loss, has_aux=True)(
            params, tokens, labels, coeff)
        return value, jax.tree_util.tree_map(jnp.add, acc, grad)
    return jax.jit(block, donate_argnums=(1,))


def train_steps(cfg: dict, opt: dict, params, steps: List[dict],
                precision: str = "fp32") -> dict:
    """Follow the program's first ``len(steps)`` coded steps, as
    ``dense_lm.train_steps`` does, with this model's block gradient.
    Returns the loss of each step, the per-leaf norms of the first
    gradient after clipping, and the final params.  ``params``
    is consumed."""
    block = make_block_grad(cfg, precision)
    first = jax.jit(lambda p, g, s, lr: dense_lm._adamw(p, g, None, None, s,
                                                        lr, 1, opt),
                    donate_argnums=(0, 1))
    later = jax.jit(lambda p, g, m, n, s, lr, t: dense_lm._adamw(
        p, g, m, n, s, lr, t, opt), donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mu = nu = None
    losses, first_grad = [], None
    for t, st in enumerate(steps):
        loss, grad = 0.0, zeros(params)
        for i in range(st["tokens"].shape[0]):
            c = float(st["coeff"][i])
            if c == 0.0:
                continue
            li, grad = block(params, grad, jnp.asarray(st["tokens"][i]),
                             jnp.asarray(st["labels"][i]), jnp.float32(c))
            loss += float(li)
        losses.append(loss)
        gn = leaf_norms(grad)
        gnorm = float(np.sqrt(np.sum(gn ** 2)))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9)) \
            if opt["clip_norm"] > 0 else 1.0
        lr = jnp.float32(lr_at(opt, t))
        if t == 0:
            first_grad = gn * scale
            params, mu, nu = first(params, grad, jnp.float32(scale), lr)
        else:
            params, mu, nu = later(params, grad, mu, nu, jnp.float32(scale),
                                   lr, jnp.float32(t + 1))
        del grad
    del mu, nu
    return {"loss": np.asarray(losses), "first_grad": first_grad,
            "params": params}
