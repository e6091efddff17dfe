"""RWKV6 (Finch) WKV Pallas kernel: chunked-parallel time mix with
data-dependent per-channel decay.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

TPU adaptation (docs/architecture.md §5): the sequential recurrence is
re-factored into per-chunk dense algebra so the MXU does all heavy work —
intra-chunk interactions become a decay-weighted lower-triangular
[c, c] @ [c, dh] matmul pair, and the [dh, dh] state is carried across
chunk programs in VMEM scratch (never touches HBM).  Grid:
(B*H "parallel", T/c "arbitrary").

Exponents are bounded by the caller's decay clamp (log w in [-5, -6e-6],
c = 16..64 -> max exponent c*5 < log(f32 max)), matching
models.rwkv6.wkv_chunked.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_wkv"]


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_out_ref,
                state_ref, *, chunk: int, nt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)        # [c, dh]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)        # [1, dh]
    s = state_ref[...]                      # [dh, dh]

    logw = jnp.log(jnp.maximum(w, 1e-12))
    c = logw.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum)
    ci = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)  # inclusive  prod_{j<=t}
    ce = ci - logw                          # exclusive  prod_{j<t}

    r_dec = r * jnp.exp(ce)
    # state entering the chunk
    o = jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk strictly-lower pairs
    k_dec = k * jnp.exp(-ci)
    scores = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(row > col, scores, 0.0)
    o = o + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # diagonal u-bonus
    bonus = jnp.sum(r * (u * k), axis=-1, keepdims=True)
    o = o + bonus * v
    o_ref[0] = o.astype(o_ref.dtype)

    # carry: S_out = diag(prod w) S_in + sum_j (prod_{l>j} w_l) k_j^T v_j
    total = ci[-1:]                          # [1, dh]
    k_carry = k * jnp.exp(total - ci)
    s_new = jnp.exp(total).T * s + jax.lax.dot_general(
        k_carry, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = s_new

    @pl.when(ti == nt - 1)
    def _emit_state():
        s_out_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(
    r: jax.Array,                 # [B, T, H, dh]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,                 # decay in (0, 1)
    u: jax.Array,                 # [H, dh] bonus
    s0: jax.Array | None = None,  # [B, H, dh, dh]
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    """Chunked WKV.  Returns (o [B,T,H,dh], s_T [B,H,dh,dh])."""
    B, T, H, dh = r.shape
    chunk = min(chunk, T)
    nt = math.ceil(T / chunk)
    pt = nt * chunk - T

    def prep(t):
        t = jnp.moveaxis(t, 2, 1).reshape(B * H, T, dh)
        if pt:
            t = jnp.pad(t, ((0, 0), (0, pt), (0, 0)))
        return t

    rt, kt, vt = prep(r), prep(k), prep(v)
    wt = jnp.moveaxis(w, 2, 1).reshape(B * H, T, dh)
    if pt:
        # pad decay with ones (no-op steps), k/v with zeros
        wt = jnp.pad(wt, ((0, 0), (0, pt), (0, 0)), constant_values=1.0)
    uu = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, 1, dh)
    s0f = (jnp.zeros((B * H, dh, dh), jnp.float32) if s0 is None
           else s0.astype(jnp.float32).reshape(B * H, dh, dh))

    o, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, nt=nt),
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nt * chunk, dh), r.dtype),
            jax.ShapeDtypeStruct((B * H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, wt, uu, s0f)

    o = o[:, :T].reshape(B, H, T, dh)
    o = jnp.moveaxis(o, 1, 2)
    s_out = s_out.reshape(B, H, dh, dh)
    return o, s_out
