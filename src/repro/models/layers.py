"""Shared neural-net layers (pure JAX, functional).

Everything is expressed as einsums over logically-annotated tensors so
GSPMD can partition them; no framework dependency.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dist import constrain

__all__ = [
    "rmsnorm", "layernorm", "norm", "rope", "yarn_inv_freq", "mlp",
    "attention", "chunked_attention", "cross_entropy",
]

_NEG_INF = -1e30


# ----------------------------- norms ---------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            io: str = "f32") -> jax.Array:
    """io='f32': the classic full-fp32 chain.  io='bf16': only the
    variance reduction runs in fp32; the normalize/scale elementwise ops
    stay in the compute dtype — halves the dominant per-layer HBM
    traffic of wide dense models (EXPERIMENTS.md Sec-Perf, command-r)."""
    dt = x.dtype
    if io == "bf16":
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(dt)
        return x * inv * (1.0 + scale.astype(dt))
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5, io: str = "f32") -> jax.Array:
    dt = x.dtype
    if io == "bf16":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(dt)
        return (x - mu.astype(dt)) * inv * scale.astype(dt) + bias.astype(dt)
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, params: dict, kind: str, io: str = "f32") -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], io=io)
    return layernorm(x, params["scale"], params["bias"], io=io)


# ----------------------------- RoPE -----------------------------------------

def yarn_inv_freq(dh: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """YaRN's rotary frequencies [dh/2] (float64) and the factor on cos
    and sin, as Hugging Face ``_compute_yarn_parameters`` computes them:
    the frequencies that turn fewer than ``beta_slow`` times over the
    original context are divided by ``factor``, those that turn more
    than ``beta_fast`` times are kept, and a linear ramp over the
    dimensions in between mixes the two."""
    half = dh // 2
    base = theta ** (-np.arange(half, dtype=np.float64) / half)

    def dim(rotations):      # the dimension that turns `rotations` times
        return dh * math.log(yarn.original_max_position_embeddings
                             / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim(yarn.beta_slow)), dh - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(half) - lo) / (hi - lo), 0.0, 1.0)
    inv = base / yarn.factor * ramp + base * (1.0 - ramp)
    scale = yarn.attention_factor
    if scale is None:
        scale = 0.1 * math.log(yarn.factor) + 1.0
    return inv, float(scale)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn=None) -> jax.Array:
    """Rotary embedding.  x: [..., S, H, dh]; positions: [S] or [B, S].
    ``yarn`` (a ``YarnConfig``): YaRN frequencies and scale."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        inv, scale = yarn_inv_freq(dh, theta, yarn)
        freq = jnp.asarray(inv, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if yarn is not None:
        sin, cos = sin * scale, cos * scale
    # broadcast over heads: [..., S, 1, half]
    sin, cos = sin[..., None, :], cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------- MLP -------------------------------------------

def mlp(x: jax.Array, params: dict, act: str) -> jax.Array:
    """Gated or plain MLP.  Weights: wi [d, F] (+wg for gated), wo [F, d]."""
    if act in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, params["wg"])
        u = jnp.einsum("...d,df->...f", x, params["wi"])
        g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
        h = g * u
    elif act == "gelu":
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["wi"])
                        + params.get("bi", 0.0))
    else:
        raise ValueError(act)
    h = constrain(h, "batch", None, "act_mlp")
    out = jnp.einsum("...f,fd->...d", h, params["wo"])
    if "bo" in params:
        out = out + params["bo"]
    return out


# --------------------------- attention ---------------------------------------

def _mask_bias(qpos: jax.Array, kpos: jax.Array, causal: bool, window: int,
               kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """[..., Sq, Sk] additive mask bias.

    ``qpos`` [..., Sq], ``kpos`` [..., Sk] and ``kv_valid`` [..., Sk] may
    each carry leading batch dims (per-row positions/validity for ragged
    left-padded serving batches); they broadcast together.
    """
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), dtype=bool)
    if causal:
        ok &= q >= k
    if window > 0:
        ok &= q - k < window
    if kv_valid is not None:
        ok = ok & kv_valid[..., None, :]
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q [B,Sq,Kv,G,dh], k [B,Sk,Kv,dh] -> [B,Kv,G,Sq,Sk] (fp32)."""
    return jnp.einsum("bqngd,bknd->bngqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def attention(
    q: jax.Array,                 # [B, Sq, H, dh]
    k: jax.Array,                 # [B, Sk, Kv, dh]
    v: jax.Array,                 # [B, Sk, Kv, dh]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset=0,                   # int or scalar array: absolute pos of q[0]
    qpos: Optional[jax.Array] = None,   # [Sq] or [B, Sq] absolute q positions
                                        # (overrides q_offset; per-row for
                                        # ragged left-padded batches)
    kpos: Optional[jax.Array] = None,   # [Sk] or [B, Sk] absolute key
                                        # positions (ring caches)
    kv_valid: Optional[jax.Array] = None,  # [Sk] or [B, Sk] bool validity
    impl: str = "xla_naive",
    q_block: int = 512,
    kv_block: int = 1024,
) -> jax.Array:
    """Grouped-query attention; returns [B, Sq, H, dh]."""
    B, Sq, H, dh = q.shape
    Kv = k.shape[2]
    G = H // Kv
    if impl in ("pallas", "pallas_interpret") and kpos is None \
            and kv_valid is None and qpos is None:
        from ..kernels import ops as _kops  # late import: no cycle
        return _kops.attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset, impl=impl)
    qg = q.reshape(B, Sq, Kv, G, dh)
    if impl == "xla_chunked" and Sq > q_block and qpos is None \
            and kv_valid is None:
        out = chunked_attention(qg, k, v, causal=causal, window=window,
                                softcap=softcap, q_offset=q_offset,
                                q_block=q_block, kv_block=kv_block)
        return out.reshape(B, Sq, H, dh)

    scale = dh ** -0.5
    scores = _gqa_scores(qg, k, scale)  # [B,Kv,G,Sq,Sk]
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    if qpos is None:
        qpos = q_offset + jnp.arange(Sq)
    if kpos is None:
        kpos = jnp.arange(k.shape[1])
    bias = _mask_bias(qpos, kpos, causal, window, kv_valid)
    if bias.ndim == 3:  # [B, Sq, Sk] per-row bias -> [B, 1, 1, Sq, Sk]
        bias = bias[:, None, None]
    scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bngqk,bknd->bqngd", probs, v)
    return out.reshape(B, Sq, H, dh)


def chunked_attention(
    qg: jax.Array,                # [B, Sq, Kv, G, dh]
    k: jax.Array,                 # [B, Sk, Kv, dh]
    v: jax.Array,
    *,
    causal: bool,
    window: int,
    softcap: float,
    q_offset,
    q_block: int,
    kv_block: int,
) -> jax.Array:
    """Online-softmax blocked attention (flash-style, XLA-level).

    Memory is O(q_block * kv_block) per step instead of O(Sq * Sk); this
    is the default train/prefill path for 4k-32k sequences and the
    reference the Pallas kernel is checked against.  The backward pass
    recomputes each block's scores (a checkpoint around each q block and
    each kv step), so it too holds one block's at a time, not the
    whole [Sq, Sk] probability matrix.
    """
    B, Sq, Kv, G, dh = qg.shape
    Sk = k.shape[1]
    scale = dh ** -0.5
    nq = -(-Sq // q_block)
    pad_q = nq * q_block - Sq
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    nk = -(-Sk // kv_block)
    pad_k = nk * kv_block - Sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qs = jnp.moveaxis(qg.reshape(B, nq, q_block, Kv, G, dh), 1, 0)

    @jax.checkpoint
    def q_step(q_i, qblk):  # qblk: [B, q_block, Kv, G, dh]
        qpos = q_offset + q_i * q_block + jnp.arange(q_block)

        @jax.checkpoint
        def kv_step(carry, kv_i):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, kv_i * kv_block, kv_block, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, kv_i * kv_block, kv_block, 1)
            s = jnp.einsum("bqngd,bknd->bngqk", qblk, ks,
                           preferred_element_type=jnp.float32) * scale
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            kpos = kv_i * kv_block + jnp.arange(kv_block)
            kvalid = kpos < Sk
            s = s + _mask_bias(qpos, kpos, causal, window, kvalid)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bngqk,bknd->bngqd", p.astype(vs.dtype), vs,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, G, q_block), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, q_block, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)  # [B, q_block, Kv, G, dh]

    outs = jax.lax.map(lambda args: q_step(*args),
                       (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_block, Kv, G, dh)
    if pad_q:
        out = out[:, :Sq]
    return out.astype(v.dtype)


# ----------------------------- loss ------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Per-token CE over a (padded) vocab.  logits [..., Vp]; labels [...]."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab:
        pad_bias = jnp.where(jnp.arange(vp) < vocab, 0.0, _NEG_INF)
        logits = logits + pad_bias
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - lab
