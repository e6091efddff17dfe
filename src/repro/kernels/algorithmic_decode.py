"""Algorithmic decoder (Lemma 12) Pallas kernels.

    u_t = u_{t-1} - A A^T u_{t-1} / nu,   u_0 = 1_k,   nu >= ||A||_2^2

||u_t||^2 decreases monotonically to err(A): t = 1 is the one-step
regime, t -> inf the optimal decode — the decoding-cost/accuracy dial of
the paper.  Realized as two fused masked matvec kernels per iterate
(A = G . diag(mask) is never materialized — the mask rides along):

    t = (G diag(m))^T u        [r-side reduction over k blocks]
    u' = u - (G diag(m)) t/nu  [k-side reduction over r blocks]

Each kernel streams G tile-by-tile through VMEM with an fp32 accumulator;
2 matvecs = 4 k*n FLOPs per iteration, bandwidth-bound like the one-step
decoder but iterated.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["algorithmic_decode", "algorithmic_iterate"]

# Mosaic contracts fp32 operands at reduced precision by default; decode
# weights and gradient messages are fp32 quantities, so ask for fp32
_FP32 = jax.lax.Precision.HIGHEST


def _atu_kernel(g_ref, m_ref, u_ref, o_ref, acc_ref, *, nk: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)           # [bk, bn]
    u = u_ref[...]                               # [1, bk]
    acc_ref[...] += jax.lax.dot_general(
        u, g, (((1,), (0,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)      # [1, bn]

    @pl.when(i == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...] * m_ref[...]   # mask the straggler cols


def _axpy_kernel(g_ref, t_ref, u_ref, o_ref, acc_ref, *, nn: int, inv_nu: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)           # [bk, bn]
    t = t_ref[...]                               # [1, bn] (already masked)
    acc_ref[...] += jax.lax.dot_general(
        g, t, (((1,), (1,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)      # [bk, 1]

    @pl.when(j == nn - 1)
    def _emit():
        o_ref[...] = u_ref[...].reshape(o_ref.shape) - inv_nu * acc_ref[...]


def algorithmic_iterate(G, mask, u, nu, *, bk=512, bn=512, interpret=False):
    """One Lemma-12 iterate u -> (I - A A^T / nu) u with A = G diag(mask)."""
    k, n = G.shape
    bk = min(bk, k)
    bn = min(bn, n)
    nk = math.ceil(k / bk)
    nn = math.ceil(n / bn)
    pk, pn = nk * bk - k, nn * bn - n
    g = jnp.pad(G.astype(jnp.float32), ((0, pk), (0, pn))) \
        if (pk or pn) else G.astype(jnp.float32)
    m = jnp.pad(mask.astype(jnp.float32), (0, pn))[None] if pn else \
        mask.astype(jnp.float32)[None]
    up = jnp.pad(u.astype(jnp.float32), (0, pk))[None] if pk else \
        u.astype(jnp.float32)[None]

    t = pl.pallas_call(
        functools.partial(_atu_kernel, nk=nk),
        grid=(nn, nk),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda jj, ii: (ii, jj)),
            pl.BlockSpec((1, bn), lambda jj, ii: (0, jj)),
            pl.BlockSpec((1, bk), lambda jj, ii: (0, ii)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda jj, ii: (0, jj)),
        out_shape=jax.ShapeDtypeStruct((1, nn * bn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(g, m, up)

    u_new = pl.pallas_call(
        functools.partial(_axpy_kernel, nn=nn, inv_nu=float(1.0 / nu)),
        grid=(nk, nn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda ii, jj: (ii, jj)),
            pl.BlockSpec((1, bn), lambda ii, jj: (0, jj)),
            pl.BlockSpec((1, bk), lambda ii, jj: (0, ii)),
        ],
        out_specs=pl.BlockSpec((bk, 1), lambda ii, jj: (ii, 0)),
        out_shape=jax.ShapeDtypeStruct((nk * bk, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(g, t, up)
    return u_new[:k, 0]


@functools.partial(jax.jit,
                   static_argnames=("nu", "iters", "bk", "bn", "interpret"))
def algorithmic_decode(
    G: jax.Array,                 # [k, n]
    mask: jax.Array,              # [n]
    nu: float,
    iters: int,
    *,
    bk: int = 512,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """u_iters from u_0 = 1_k.  ||u_t||^2 upper-bounds err(A) (Lemma 12)."""
    k = G.shape[0]
    u = jnp.ones((k,), jnp.float32)
    for _ in range(iters):
        u = algorithmic_iterate(G, mask, u, nu, bk=bk, bn=bn,
                                interpret=interpret)
    return u
