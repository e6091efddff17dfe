"""Batched decode Pallas kernels: one launch, B straggler masks.

The scalar kernels (onestep_decode.py / algorithmic_decode.py) decode a
single mask per launch — fine for a training step, wasteful for the
Monte-Carlo ensembles behind Figs. 2-5 and the delta-sweeps, where the
same G is decoded against thousands of masks.  These kernels add a
leading batch grid dimension so every mask in a [B, n] ensemble is
decoded in one launch:

    batched_onestep_decode      V = diag(rho) * M G^T          [B, k]
    batched_onestep_decode_ell  same, via the row-ELL packing of G
                                (reads k*rmax packed entries of G from
                                HBM instead of the dense k*n)
    batched_algorithmic_decode  U_t per mask, Lemma-12 iterates [B, k]
    batched_masked_gram         diag(m_b) Gram diag(m_b)       [B, n, n]
                                (the normal-equations ensemble feeding
                                the batched least-squares decoder)

All kernels tile (batch, k) in parallel and reduce sequentially over
the contracted dimension in an fp32 VMEM accumulator; G is never
replicated per mask — the mask rides along as a [bb, bn] block, exactly
the streaming property the paper claims for one-step decoding, amortized
across the batch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "batched_onestep_decode",
    "batched_onestep_decode_ell",
    "batched_algorithmic_decode",
    "batched_algorithmic_iterate",
    "batched_masked_gram",
]

# Mosaic contracts fp32 operands at reduced precision by default; decode
# weights and gradient messages are fp32 quantities, so ask for fp32
_FP32 = jax.lax.Precision.HIGHEST


def _pad2(x, r, c):
    pr, pc = r - x.shape[0], c - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


# --------------------------------------------------------------------------
# dense batched one-step:  V[b, i] = rho_b * sum_j G[i, j] m[b, j]
# --------------------------------------------------------------------------

def _onestep_batch_kernel(m_ref, g_ref, r_ref, o_ref, acc_ref, *, nn: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m = m_ref[...]                               # [bb, bn]
    g = g_ref[...].astype(jnp.float32)           # [bk, bn]
    acc_ref[...] += jax.lax.dot_general(
        m, g, (((1,), (1,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)      # [bb, bk]

    @pl.when(j == nn - 1)
    def _emit():
        o_ref[...] = acc_ref[...] * r_ref[...]   # [bb, 1] rho broadcast


@functools.partial(jax.jit, static_argnames=("bb", "bk", "bn", "interpret"))
def batched_onestep_decode(
    G: jax.Array,          # [k, n]
    masks: jax.Array,      # [B, n] bool/0-1
    rhos: jax.Array,       # [B]
    *,
    bb: int = 128,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """V[b] = rho_b * G @ m_b for every mask in the batch.  [B, k] fp32."""
    k, n = G.shape
    B = masks.shape[0]
    bb, bk, bn = min(bb, B), min(bk, k), min(bn, n)
    nb, nk, nn = map(math.ceil, (B / bb, k / bk, n / bn))
    g = _pad2(G.astype(jnp.float32), nk * bk, nn * bn)
    m = _pad2(masks.astype(jnp.float32), nb * bb, nn * bn)
    r = _pad2(rhos.astype(jnp.float32)[:, None], nb * bb, 1)

    out = pl.pallas_call(
        functools.partial(_onestep_batch_kernel, nn=nn),
        grid=(nb, nk, nn),
        in_specs=[
            pl.BlockSpec((bb, bn), lambda b, i, j: (b, j)),
            pl.BlockSpec((bk, bn), lambda b, i, j: (i, j)),
            pl.BlockSpec((bb, 1), lambda b, i, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bk), lambda b, i, j: (b, i)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, nk * bk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(m, g, r)
    return out[:B, :k]


# --------------------------------------------------------------------------
# ELL batched one-step: read G as its row-ELL packing (k*rmax entries)
# instead of streaming the dense zero entries of G from HBM.
# --------------------------------------------------------------------------

def _onestep_ell_kernel(m_ref, i_ref, v_ref, r_ref, o_ref):
    m = m_ref[...]                               # [bb, n]
    n, (rmax, bk) = m.shape[1], i_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, bk), 0)

    # Mosaic has no in-kernel gather: unpack the [n, bk] block of G^T in
    # VMEM, one ELL slot at a time (padding slots add val 0), and let
    # the MXU contract it against the masks
    def unpack(r, gt):
        idx = i_ref[pl.ds(r, 1), :]              # [1, bk] int32
        val = v_ref[pl.ds(r, 1), :]              # [1, bk]
        return gt + jnp.where(rows == idx, val, 0.0)

    gt = jax.lax.fori_loop(0, rmax, unpack, jnp.zeros((n, bk), jnp.float32))
    v = jax.lax.dot_general(m, gt, (((1,), (0,)), ((), ())),
                            precision=_FP32,
                            preferred_element_type=jnp.float32)  # [bb, bk]
    o_ref[...] = v * r_ref[...]


@functools.partial(jax.jit, static_argnames=("bb", "bk", "interpret"))
def batched_onestep_decode_ell(
    ell_idx: jax.Array,    # [k, rmax] int32 column indices (0-padded)
    ell_val: jax.Array,    # [k, rmax] coefficients (0-padded)
    masks: jax.Array,      # [B, n]
    rhos: jax.Array,       # [B]
    *,
    bb: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sparse batched Algorithm 1 via the GradientCode.ell() packing.

    Padding slots carry (idx 0, val 0), so they add exactly 0.  The mask
    block and the unpacked [n, bk] block of G^T span the full worker
    dimension (n is at most a few thousand — the paper's regime — so
    both fit VMEM comfortably).
    """
    k, rmax = ell_idx.shape
    B, n = masks.shape
    bb, bk = min(bb, B), min(bk, k)
    nb, nk = math.ceil(B / bb), math.ceil(k / bk)
    # slot-major [rmax, k]: each ELL slot is one lane-dense row
    idx = _pad2(ell_idx.astype(jnp.int32).T, rmax, nk * bk)
    val = _pad2(ell_val.astype(jnp.float32).T, rmax, nk * bk)
    m = _pad2(masks.astype(jnp.float32), nb * bb, n)
    r = _pad2(rhos.astype(jnp.float32)[:, None], nb * bb, 1)

    out = pl.pallas_call(
        _onestep_ell_kernel,
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((bb, n), lambda b, i: (b, 0)),
            pl.BlockSpec((rmax, bk), lambda b, i: (0, i)),
            pl.BlockSpec((rmax, bk), lambda b, i: (0, i)),
            pl.BlockSpec((bb, 1), lambda b, i: (b, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bk), lambda b, i: (b, i)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, nk * bk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(m, idx, val, r)
    return out[:B, :k]


# --------------------------------------------------------------------------
# batched masked Gram: Mg[b] = diag(m_b) Gram diag(m_b), the per-mask
# normal-equation matrices of the least-squares decoder.  Pure VPU
# (elementwise outer masking) — the O(k n^2) Gram contraction happens
# ONCE outside the kernel, so the ensemble costs O(B n^2) reads/writes.
# --------------------------------------------------------------------------

def _masked_gram_kernel(mi_ref, mj_ref, g_ref, o_ref):
    mi = mi_ref[...]                             # [bb, bi]
    mj = mj_ref[...]                             # [bb, bj]
    g = g_ref[...].astype(jnp.float32)           # [bi, bj]
    o_ref[...] = mi[:, :, None] * mj[:, None, :] * g[None, :, :]


@functools.partial(jax.jit, static_argnames=("bb", "bi", "bj", "interpret"))
def batched_masked_gram(
    gram: jax.Array,       # [n, n] = G^T G (precomputed once per code)
    masks: jax.Array,      # [B, n] bool/0-1
    *,
    bb: int = 8,
    bi: int = 128,
    bj: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Mg[b] = m_b m_b^T ⊙ Gram for every mask in the batch.  [B, n, n]
    fp32.  Straggler rows/columns come out exactly zero; the solver adds
    the ridge/unit diagonal on the host (core.decoding.solve_masked_gram).
    """
    n = gram.shape[0]
    B = masks.shape[0]
    bb, bi, bj = min(bb, B), min(bi, n), min(bj, n)
    nb, ni, nj = map(math.ceil, (B / bb, n / bi, n / bj))
    pad_m = max(ni * bi, nj * bj)
    g = _pad2(gram.astype(jnp.float32), ni * bi, nj * bj)
    m = _pad2(masks.astype(jnp.float32), nb * bb, pad_m)

    out = pl.pallas_call(
        _masked_gram_kernel,
        grid=(nb, ni, nj),
        in_specs=[
            pl.BlockSpec((bb, bi), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, bj), lambda b, i, j: (b, j)),
            pl.BlockSpec((bi, bj), lambda b, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, bi, bj), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, ni * bi, nj * bj),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(m, m, g)
    return out[:B, :n, :n]


# --------------------------------------------------------------------------
# batched algorithmic decoder: U_t = U_{t-1} - (A_b A_b^T / nu_b) U_{t-1}
# per mask, realized as two fused masked matmul kernels per iterate.
# --------------------------------------------------------------------------

def _batched_atu_kernel(u_ref, g_ref, m_ref, o_ref, acc_ref, *, nk: int):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    u = u_ref[...]                               # [bb, bk]
    g = g_ref[...].astype(jnp.float32)           # [bk, bn]
    acc_ref[...] += jax.lax.dot_general(
        u, g, (((1,), (0,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)      # [bb, bn]

    @pl.when(i == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...] * m_ref[...]   # mask straggler columns


def _batched_axpy_kernel(t_ref, g_ref, u_ref, inv_ref, o_ref, acc_ref,
                         *, nn: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = t_ref[...]                               # [bb, bn] (masked)
    g = g_ref[...].astype(jnp.float32)           # [bk, bn]
    acc_ref[...] += jax.lax.dot_general(
        t, g, (((1,), (1,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)      # [bb, bk]

    @pl.when(j == nn - 1)
    def _emit():
        o_ref[...] = u_ref[...] - acc_ref[...] * inv_ref[...]


def batched_algorithmic_iterate(G, masks, U, inv_nus, *, bb=128, bk=256,
                                bn=256, interpret=False):
    """One Lemma-12 iterate for every mask: U -> U - (A A^T U) / nu.

    G [k, n], masks [B, n] already float32 (possibly padded), U [B, k],
    inv_nus [B, 1].  Shapes must be pre-padded to block multiples.
    Returns (U_new, T) with T = (U G) * masks — the masked A^T u term,
    whose running sum / nu is the decode-weight iterate x_t (Lemma 12),
    accumulated by the caller.
    """
    B, k = U.shape
    n = G.shape[1]
    nb, nk, nn = B // bb, k // bk, n // bn

    T = pl.pallas_call(
        functools.partial(_batched_atu_kernel, nk=nk),
        grid=(nb, nn, nk),
        in_specs=[
            pl.BlockSpec((bb, bk), lambda b, j, i: (b, i)),
            pl.BlockSpec((bk, bn), lambda b, j, i: (i, j)),
            pl.BlockSpec((bb, bn), lambda b, j, i: (b, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda b, j, i: (b, j)),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(U, G, masks)

    U_new = pl.pallas_call(
        functools.partial(_batched_axpy_kernel, nn=nn),
        grid=(nb, nk, nn),
        in_specs=[
            pl.BlockSpec((bb, bn), lambda b, i, j: (b, j)),
            pl.BlockSpec((bk, bn), lambda b, i, j: (i, j)),
            pl.BlockSpec((bb, bk), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, 1), lambda b, i, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bk), lambda b, i, j: (b, i)),
        out_shape=jax.ShapeDtypeStruct((B, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(T, G, U, inv_nus)
    return U_new, T


@functools.partial(jax.jit,
                   static_argnames=("iters", "bb", "bk", "bn", "interpret",
                                    "return_weights"))
def batched_algorithmic_decode(
    G: jax.Array,          # [k, n]
    masks: jax.Array,      # [B, n]
    nus: jax.Array,        # [B] per-mask nu >= ||A_b||_2^2
    iters: int,
    *,
    bb: int = 128,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
    return_weights: bool = False,
):
    """U_iters from U_0 = 1 for every mask in the batch.  [B, k] fp32.

    With return_weights=True also returns the decode weights
    X = sum_t T_t / nu (masked), as (U, X [B, n]).
    """
    k, n = G.shape
    B = masks.shape[0]
    bb, bk, bn = min(bb, B), min(bk, k), min(bn, n)
    nb, nk, nn = map(math.ceil, (B / bb, k / bk, n / bn))
    g = _pad2(G.astype(jnp.float32), nk * bk, nn * bn)
    m = _pad2(masks.astype(jnp.float32), nb * bb, nn * bn)
    # padded batch rows get nu = 1 (harmless: their masks are all-zero)
    inv = jnp.where(nus > 0, 1.0 / nus, 1.0).astype(jnp.float32)[:, None]
    inv = jnp.pad(inv, ((0, nb * bb - B), (0, 0)), constant_values=1.0)
    U = jnp.zeros((nb * bb, nk * bk), jnp.float32) \
        .at[:, :k].set(1.0)  # padded k entries stay 0
    X = jnp.zeros_like(m)
    for _ in range(iters):
        U, T = batched_algorithmic_iterate(g, m, U, inv, bb=bb, bk=bk, bn=bn,
                                           interpret=interpret)
        if return_weights:
            X = X + T * inv
    if return_weights:
        return U[:B, :k], X[:B, :n]
    return U[:B, :k]
