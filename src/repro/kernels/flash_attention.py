"""Flash attention Pallas TPU kernel (online softmax, blocked VMEM tiling).

Design for the TPU memory hierarchy (docs/architecture.md §5):
  * grid = (B, H, Sq/bq, Sk/bk); the last dim is sequential ("arbitrary")
    so the fp32 running max / denominator / accumulator for one q-block
    live in VMEM scratch across kv-block iterations;
  * q/k/v blocks are streamed HBM -> VMEM by the BlockSpec pipeline with
    MXU-aligned tiles (bq, bk multiples of 128 at production shapes;
    head_dim 64/128 rides the lane dimension);
  * GQA is expressed in the k/v index_map (h -> h // group) — no
    materialized head broadcast;
  * causal / sliding-window block skipping: fully-masked kv blocks are
    skipped via pl.when, halving prefill work at 32k.

Validated against ref.attention_ref in interpret mode on CPU (the TPU is
the target, not the runtime — per the brief).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, qpos_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, bq: int, bk: int, nk: int, sq: int, sk: int,
                 causal: bool, window: int, softcap: float, scale: float):
    """One (batch, head, q-block) x sequential kv-block program."""
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos_row = qpos_ref[0]                      # [bq] absolute positions
    qpos = jnp.broadcast_to(qpos_row[:, None], (bq, bk))
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: a kv block is dead if it is entirely in the causal
    # future or entirely outside the sliding window for every q row.
    q_lo, q_hi = qpos_row[0], qpos_row[bq - 1]
    k_lo = kj * bk
    live = jnp.asarray(True)
    if causal:
        live = k_lo <= q_hi
    if window > 0:
        live = jnp.logical_and(
            live, kj * bk + bk - 1 >= q_lo - window + 1)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        ok = kpos < sk                                  # kv padding
        if causal:
            ok = jnp.logical_and(ok, qpos >= kpos)
        if window > 0:
            ok = jnp.logical_and(ok, qpos - kpos < window)
        s = jnp.where(ok, s, _NEG_INF)

        m_prev = m_ref[...]                             # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,                 # [B, Sq, H, dh]
    k: jax.Array,                 # [B, Sk, Kv, dh]
    v: jax.Array,                 # [B, Sk, Kv, dh]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int | jax.Array = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Blocked online-softmax attention.  Returns [B, Sq, H, dh]."""
    B, Sq, H, dh = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    if H % Kv:
        raise ValueError(f"H {H} % Kv {Kv}")
    G = H // Kv
    bq = min(bq, max(Sq, 8))
    bk = min(bk, max(Sk, 8))

    # layout: heads leading so blocks are contiguous [s, dh] tiles
    qt = jnp.moveaxis(q, 2, 1)                    # [B, H, Sq, dh]
    kt = jnp.moveaxis(k, 2, 1)                    # [B, Kv, Sk, dh]
    vt = jnp.moveaxis(v, 2, 1)

    nq = math.ceil(Sq / bq)
    nk = math.ceil(Sk / bk)
    pq, pk = nq * bq - Sq, nk * bk - Sk
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))

    # absolute q positions as a dynamic input (supports traced decode
    # offsets); padded rows get positions past Sq — outputs are trimmed.
    qpos = (jnp.asarray(q_offset, jnp.int32)
            + jnp.arange(nq * bq, dtype=jnp.int32))[None]   # [1, nq*bq]

    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, nk=nk, sq=Sq, sk=Sk, causal=causal,
        window=window, softcap=softcap, scale=dh ** -0.5)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, bq), lambda b, h, i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # denominator l
            pltpu.VMEM((bq, dh), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, qpos)

    out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)                # [B, Sq, H, dh]
