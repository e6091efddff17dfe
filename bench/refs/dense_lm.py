"""Plain float32 reference of a dense decoder-only language model's
coded training step: weighted loss, gradient and AdamW, in straight
``jax.numpy`` with no kernels, caches, scans or remat.

It imports nothing of the system under test.  It reads weights in the
tree layout the system under test consumes, because the benchmark makes
those weights (``init_params``) and hands the same tree to both:

    embed [Vp, d]                    token embedding; rows >= vocab pad
    final_norm {scale[, bias]}
    head [d, Vp]                     only when the head is untied
    stack/p0/ln1, ln2 {scale[, bias]}          leading dim = layers
    stack/p0/attn {wq, wk, wv, wo[, bq, bk, bv]}
    stack/p0/mlp  {wg, wi, wo}  (SwiGLU)  or  {wi, bi, wo, bo}  (GeLU)
    rem {}

Mechanisms, as the configuration states them:

* RMSNorm ``x / rms(x) * (1 + scale)``, eps 1e-6 (the stored ``scale``
  is the offset from 1); LayerNorm ``(x - mu) / sigma * scale + bias``,
  eps 1e-5.
* Attention: RoPE on q and k (rotate-half pairing, base ``rope_theta``),
  causal softmax over ``q k^T / sqrt(d_head)``, grouped-query heads
  (``n_heads / n_kv`` query heads share a key/value head), optional
  q/k/v bias.
* MLP: SwiGLU ``(silu(x wg) * (x wi)) wo`` or GeLU (tanh form)
  ``gelu(x wi + bi) wo + bo``.
* Pre-norm residual blocks; the head is ``embed^T`` when tied.
* Loss: per-row mean token cross-entropy over the real vocabulary,
  combined with the per-task decode coefficients.

Every matmul runs at ``jax.default_matmul_precision("highest")``.  The
control (``precision="fp8"``) runs the same model with every matmul's
operands rounded to float8 with a per-tensor scale (e4m3 forward, e5m2
cotangents), the step below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

_F8_FWD, _F8_BWD = jnp.float8_e4m3fn, jnp.float8_e5m2


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_to"]
    return -(-cfg["vocab"] // m) * m


def param_shapes(cfg: dict) -> dict:
    """The weight tree's shapes, one entry per leaf."""
    d, L = cfg["d_model"], cfg["n_layers"]
    qd, kvd = cfg["n_heads"] * cfg["d_head"], cfg["n_kv"] * cfg["d_head"]
    f, vp = cfg["d_ff"], padded_vocab(cfg)
    ln = cfg["norm"] == "layernorm"

    def norm(prefix):
        out = {"scale": prefix + (d,)}
        if ln:
            out["bias"] = prefix + (d,)
        return out

    attn = {"wq": (L, d, qd), "wk": (L, d, kvd), "wv": (L, d, kvd),
            "wo": (L, qd, d)}
    if cfg["qkv_bias"]:
        attn.update(bq=(L, qd), bk=(L, kvd), bv=(L, kvd))
    if cfg["act"] == "swiglu":
        mlp = {"wg": (L, d, f), "wi": (L, d, f), "wo": (L, f, d)}
    else:
        mlp = {"wi": (L, d, f), "bi": (L, f), "wo": (L, f, d), "bo": (L, d)}
    tree = {"embed": (vp, d), "final_norm": norm(()),
            "stack": {"p0": {"ln1": norm((L,)), "attn": attn,
                             "ln2": norm((L,)), "mlp": mlp}},
            "rem": {}}
    if not cfg["tie_embeddings"]:
        tree["head"] = (d, vp)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Weights from a key, float32: matrices normal with std
    1/sqrt(fan-in), the embedding std 0.02, RMSNorm offsets and every
    bias zero, LayerNorm scales one.  Deterministic in the key; jit it."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, shape), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if name == "['embed']":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        elif len(shape) >= 2 and not name.endswith(("['bi']", "['bo']",
                                                    "['bq']", "['bk']",
                                                    "['bv']", "['scale']",
                                                    "['bias']")):
            std = 1.0 / math.sqrt(shape[-2])
            out.append(std * jax.random.normal(k, shape, jnp.float32))
        elif name.endswith("['scale']") and cfg["norm"] == "layernorm":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jnp.zeros(shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# matmul precision: the reference's, and the control's
# --------------------------------------------------------------------------


def _q(x, dtype):
    """Round to ``dtype`` under a per-tensor scale that maps amax to the
    format's largest finite value; returned in float32."""
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _fp8_einsum(spec: str) -> Callable:
    lhs, out = spec.split("->")
    a_s, b_s = lhs.split(",")

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _q(a, _F8_FWD), _q(b, _F8_FWD))

    def fwd(a, b):
        qa, qb = _q(a, _F8_FWD), _q(b, _F8_FWD)
        return jnp.einsum(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        qg = _q(g, _F8_BWD)
        return (jnp.einsum(f"{out},{b_s}->{a_s}", qg, qb),
                jnp.einsum(f"{a_s},{out}->{b_s}", qa, qg))

    f.defvjp(fwd, bwd)
    return f


def _einsum_fn(precision: str) -> Callable:
    if precision == "fp32":
        return jnp.einsum
    if precision == "fp8":
        cache: Dict[str, Callable] = {}

        def es(spec, a, b):
            if spec not in cache:
                cache[spec] = _fp8_einsum(spec)
            return cache[spec](a, b)
        return es
    raise ValueError(f"precision {precision!r} not in ('fp32', 'fp8')")


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def _norm(x, p, kind):
    if kind == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + 1e-6) * (1.0 + p["scale"])
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x [B, S, H, dh]: rotate pairs (i, i + dh/2) by position * freq_i."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, cfg, es):
    B, S, _ = x.shape
    H, Kv, dh = cfg["n_heads"], cfg["n_kv"], cfg["d_head"]
    a = p["attn"]
    h = _norm(x, p["ln1"], cfg["norm"])
    q = es("bsd,de->bse", h, a["wq"])
    k = es("bsd,de->bse", h, a["wk"])
    v = es("bsd,de->bse", h, a["wv"])
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, H, dh), cfg["rope_theta"])
    k = _rope(k.reshape(B, S, Kv, dh), cfg["rope_theta"])
    v = v.reshape(B, S, Kv, dh)
    # each key/value head serves H / Kv consecutive query heads
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    scores = es("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = np.tril(np.ones((S, S), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = es("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * dh)
    x = x + es("bse,ed->bsd", att, a["wo"])

    m = p["mlp"]
    h = _norm(x, p["ln2"], cfg["norm"])
    if cfg["act"] == "swiglu":
        g = jax.nn.silu(es("bsd,df->bsf", h, m["wg"]))
        u = es("bsd,df->bsf", h, m["wi"])
        ff = es("bsf,fd->bsd", g * u, m["wo"])
    else:
        u = jax.nn.gelu(es("bsd,df->bsf", h, m["wi"]) + m["bi"],
                        approximate=True)
        ff = es("bsf,fd->bsd", u, m["wo"]) + m["bo"]
    return x + ff


def row_ce(params, cfg: dict, tokens, labels, precision: str = "fp32"):
    """[B] mean token cross-entropy of each row."""
    es = _einsum_fn(precision)
    V = cfg["vocab"]
    x = params["embed"][tokens]
    layers = params["stack"]["p0"]
    for i in range(cfg["n_layers"]):
        x = _layer(jax.tree_util.tree_map(lambda t: t[i], layers), x, cfg,
                   es)
    x = _norm(x, params["final_norm"], cfg["norm"])
    head = (params["embed"].T if cfg["tie_embeddings"]
            else params["head"])[:, :V]
    logits = es("bsd,dv->bsv", x, head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - lab, axis=-1)


# --------------------------------------------------------------------------
# the coded training step
# --------------------------------------------------------------------------


def make_block_grad(cfg: dict, precision: str = "fp32") -> Callable:
    """jitted (params, acc, tokens [R, S], labels [R, S], coeff []) ->
    (coeff * sum of the rows' CEs, acc + its gradient): one task's rows,
    added into the donated accumulator ``acc``."""
    def loss(params, tokens, labels, coeff):
        with jax.default_matmul_precision("highest"):
            return coeff * jnp.sum(row_ce(params, cfg, tokens, labels,
                                          precision))

    def block(params, acc, tokens, labels, coeff):
        value, grad = jax.value_and_grad(loss)(params, tokens, labels, coeff)
        return value, jax.tree_util.tree_map(jnp.add, acc, grad)
    return jax.jit(block, donate_argnums=(1,))


def lr_at(opt: dict, step: int) -> float:
    """Learning rate of step ``step`` during linear warm-up (the steps
    the comparison covers lie inside it, before any decay)."""
    if step >= opt["warmup_steps"]:
        raise ValueError("the reference follows the warm-up steps only")
    return opt["lr"] * (step + 1) / opt["warmup_steps"]


@jax.jit
def _leaf_norms(tree):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                          for x in jax.tree_util.tree_leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    """Per-leaf L2 norms (float64 on the host), in flatten order."""
    return np.asarray(jax.device_get(_leaf_norms(tree)), dtype=np.float64)


def _adamw(params, grad, mu, nu, scale, lr, step, opt: dict):
    """AdamW on every leaf; weight decay on every leaf stored with two or
    more dimensions, as the configuration states.  ``mu``/``nu`` None
    means zero moments (the first step)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    pl, td = jax.tree_util.tree_flatten(params)
    gl = jax.tree_util.tree_leaves(grad)
    ml = [None] * len(pl) if mu is None else jax.tree_util.tree_leaves(mu)
    nl = [None] * len(pl) if nu is None else jax.tree_util.tree_leaves(nu)
    outs = []
    for p, g, m, n in zip(pl, gl, ml, nl):
        g = g * scale
        m = (1 - b1) * g if m is None else b1 * m + (1 - b1) * g
        n = (1 - b2) * g * g if n is None else b2 * n + (1 - b2) * g * g
        delta = (m / (1 - b1 ** step)) / (jnp.sqrt(n / (1 - b2 ** step))
                                          + eps)
        if p.ndim >= 2 and wd > 0:
            delta = delta + wd * p
        outs.append((p - lr * delta, m, n))
    return tuple(jax.tree_util.tree_unflatten(td, [o[i] for o in outs])
                 for i in range(3))


def train_steps(cfg: dict, opt: dict, params, steps: List[dict],
                precision: str = "fp32") -> dict:
    """Follow the program's first ``len(steps)`` coded steps.

    ``steps[t]`` holds the unique tasks of step t: ``tokens`` /
    ``labels`` [k, R, S] and ``coeff`` [k], the loss weight of each of
    the task's rows (its decode coefficient over k*T).  Returns the
    loss of each step, the per-leaf norms of the first gradient after
    clipping (what the optimizer gets), and the final params.
    ``params`` is consumed.
    """
    block = make_block_grad(cfg, precision)
    first = jax.jit(lambda p, g, s, lr: _adamw(p, g, None, None, s, lr, 1,
                                               opt),
                    donate_argnums=(0, 1))
    later = jax.jit(lambda p, g, m, n, s, lr, t: _adamw(p, g, m, n, s, lr,
                                                        t, opt),
                    donate_argnums=(0, 2, 3))
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
