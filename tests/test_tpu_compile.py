"""Compile every Pallas kernel of the decode and attention paths for a
described TPU v5e, with no chip attached.

Each test lowers one kernel at the widths the chip runs (n = k = 256
workers, B = 1000 masks; the minicpm-2b / recurrentgemma-9b / rwkv6-3b
head shapes for the sequence kernels), with the tiles the chip itself
would resolve (``tiles.resolve(..., backend="tpu-v5e")``), and asserts
that the compiled HLO holds the Mosaic kernel (``tpu_custom_call``).
The TPU compiler refuses here what it would refuse on the chip:
unsupported primitives, unaligned slices, VMEM overruns.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import tiles as T

N = K = 256          # workers and tasks of the decode cell
B = 1000             # straggler masks per decode_batch
P = 1 << 18          # flat gradient width of the accumulate kernels
S = 2048             # sequence length of the attention-path kernels


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on libtpu
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _tiles(kernel, batch=None):
    return T.resolve(kernel, None, backend="tpu-v5e", B=batch)


def _assert_kernel(sharding, fn, *shapes):
    """Compile fn for the described chip; its HLO holds the Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _bgc_ell():
    from repro.core import registry

    return registry.make("bgc", k=K, n=N, s=8, seed=0).ell()


f32, bf16, i32, b1 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.bool_


def test_batched_onestep_decode(one_chip):
    from repro.kernels.batched_decode import batched_onestep_decode

    kw = _tiles("batched_onestep_decode", B)
    _assert_kernel(
        one_chip, lambda g, m, r: batched_onestep_decode(g, m, r, **kw),
        ((K, N), f32), ((B, N), b1), ((B,), f32))


def test_batched_onestep_decode_ell(one_chip):
    from repro.kernels.batched_decode import batched_onestep_decode_ell

    idx, _ = _bgc_ell()
    rmax = idx.shape[1]
    assert 4 * rmax <= N        # the engine routes this code through ELL
    kw = _tiles("batched_onestep_decode_ell", B)
    _assert_kernel(
        one_chip,
        lambda i, v, m, r: batched_onestep_decode_ell(i, v, m, r, **kw),
        ((K, rmax), i32), ((K, rmax), f32), ((B, N), b1), ((B,), f32))


def test_batched_algorithmic_decode(one_chip):
    from repro.kernels.batched_decode import batched_algorithmic_decode

    kw = _tiles("batched_algorithmic_decode", B)
    _assert_kernel(
        one_chip,
        lambda g, m, nu: batched_algorithmic_decode(
            g, m, nu, 4, return_weights=True, **kw),
        ((K, N), f32), ((B, N), b1), ((B,), f32))


@pytest.mark.parametrize("n", [N, 1024])
def test_batched_masked_gram(one_chip, n):
    from repro.kernels.batched_decode import batched_masked_gram

    kw = _tiles("batched_masked_gram", B)
    _assert_kernel(
        one_chip, lambda g, m: batched_masked_gram(g, m, **kw),
        ((n, n), f32), ((B, n), b1))


def test_onestep_decode(one_chip):
    from repro.kernels.onestep_decode import onestep_decode

    kw = _tiles("onestep_decode")
    _assert_kernel(
        one_chip, lambda g, m: onestep_decode(g, m, 1.5, **kw),
        ((K, N), f32), ((N,), b1))


def test_algorithmic_decode(one_chip):
    from repro.kernels.algorithmic_decode import algorithmic_decode

    kw = _tiles("algorithmic_decode")
    _assert_kernel(
        one_chip, lambda g, m: algorithmic_decode(g, m, 9.0, 4, **kw),
        ((K, N), f32), ((N,), b1))


def test_coded_accumulate(one_chip):
    from repro.kernels.coded_accumulate import coded_accumulate

    kw = _tiles("coded_accumulate")
    _assert_kernel(
        one_chip, lambda g, w: coded_accumulate(g, w, **kw),
        ((K, P), f32), ((K,), f32))


def test_coded_accumulate_batched(one_chip):
    from repro.kernels.coded_accumulate import coded_accumulate_batched

    kw = _tiles("coded_accumulate_batched", B)
    _assert_kernel(
        one_chip, lambda g, w: coded_accumulate_batched(g, w, **kw),
        ((K, P), f32), ((B, K), f32))


def test_fused_decode_apply(one_chip):
    from repro.kernels.fused_decode_apply import fused_decode_apply

    kw = _tiles("fused_decode_apply", B)
    _assert_kernel(
        one_chip, lambda g, m, s: fused_decode_apply(g, m, s, **kw),
        ((N, P), f32), ((B, N), b1), ((B,), f32))


def test_flash_attention_minicpm(one_chip):
    from repro.kernels.flash_attention import flash_attention

    # minicpm-2b: 36 heads of 64, MHA
    _assert_kernel(
        one_chip, lambda q, k, v: flash_attention(q, k, v, causal=True),
        ((1, S, 36, 64), bf16), ((1, S, 36, 64), bf16),
        ((1, S, 36, 64), bf16))


def test_rglru_scan_recurrentgemma(one_chip):
    from repro.kernels.rglru_scan import rglru_scan

    # recurrentgemma-9b: recurrence width 4096
    _assert_kernel(
        one_chip, lambda u, la, h0: rglru_scan(u, la, h0),
        ((1, S, 4096), f32), ((1, S, 4096), f32), ((1, 4096), f32))


def test_rwkv6_wkv_rwkv6(one_chip):
    from repro.kernels.rwkv6_wkv import rwkv6_wkv

    # rwkv6-3b: 40 heads of 64
    shp = ((1, S, 40, 64), f32)
    _assert_kernel(
        one_chip, lambda r, k, v, w, u: rwkv6_wkv(r, k, v, w, u),
        shp, shp, shp, shp, ((40, 64), f32))


def test_tiles_resolve_for_the_chip_not_the_host():
    """The chip resolves its own tile key: the interpreter-tuned host
    tiles would overrun v5e VMEM in batched_masked_gram."""
    chip = T.resolve("batched_masked_gram", None, backend="tpu-v5e", B=B)
    assert chip == T.DEFAULT_TILES["batched_masked_gram"].kwargs(
        "batched_masked_gram")
    assert np.prod([chip[a] for a in ("bb", "bi", "bj")]) * 4 < 16 * 2**20
