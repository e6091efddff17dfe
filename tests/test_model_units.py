"""Unit tests for model internals: sequence-impl equivalences and the MoE
dispatch against its dense oracle."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ArchConfig, MoEConfig
from repro.models.layers import _mask_bias, attention, live_kv_blocks
from repro.models.rglru import rglru_scan_ref
from repro.models.rwkv6 import wkv_chunked, wkv_scan_ref
from repro.models import moe as moe_lib
from repro.models.spec import init_params


RNG = np.random.default_rng


class TestWKV:
    def _inputs(self, B=2, T=32, H=3, dh=8, seed=0):
        r = RNG(seed)
        mk = lambda: jnp.asarray(r.normal(size=(B, T, H, dh)) * 0.5, jnp.float32)
        w = jnp.asarray(r.uniform(0.2, 0.98, size=(B, T, H, dh)), jnp.float32)
        u = jnp.asarray(r.normal(size=(H, dh)) * 0.3, jnp.float32)
        return mk(), mk(), mk(), w, u

    @pytest.mark.parametrize("T,chunk", [(32, 16), (64, 16), (48, 16)])
    def test_chunked_matches_scan(self, T, chunk):
        r, k, v, w, u = self._inputs(T=T)
        o_ref, s_ref = wkv_scan_ref(r, k, v, w, u)
        o_chk, s_chk = wkv_chunked(r, k, v, w, u, chunk=chunk)
        np.testing.assert_allclose(np.asarray(o_chk), np.asarray(o_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_chunked_with_initial_state(self):
        r, k, v, w, u = self._inputs(T=32, seed=1)
        s0 = jnp.asarray(RNG(2).normal(size=(2, 3, 8, 8)), jnp.float32)
        o_ref, s_ref = wkv_scan_ref(r, k, v, w, u, s0=s0)
        o_chk, s_chk = wkv_chunked(r, k, v, w, u, s0=s0, chunk=16)
        np.testing.assert_allclose(np.asarray(o_chk), np.asarray(o_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_strong_decay_no_overflow(self):
        """Clamped decay range keeps the chunked factorization finite."""
        r, k, v, _, u = self._inputs(T=32, seed=3)
        w = jnp.full(r.shape, np.exp(-5.0), jnp.float32)  # strongest decay
        o_chk, s_chk = wkv_chunked(r, k, v, w, u, chunk=16)
        assert np.isfinite(np.asarray(o_chk)).all()
        o_ref, _ = wkv_scan_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o_chk), np.asarray(o_ref),
                                   rtol=1e-3, atol=1e-4)

    def test_state_continuation(self):
        """Running two halves with carried state == one full pass."""
        r, k, v, w, u = self._inputs(T=32, seed=4)
        o_full, s_full = wkv_scan_ref(r, k, v, w, u)
        o1, s1 = wkv_chunked(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u)
        o2, s2 = wkv_chunked(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u, s0=s1)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                                   np.asarray(o_full), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   rtol=1e-4, atol=1e-4)


class TestRGLRU:
    def test_assoc_scan_matches_loop(self):
        r = RNG(0)
        B, S, D = 2, 17, 5
        log_a = jnp.asarray(-r.uniform(0.01, 2.0, (B, S, D)), jnp.float32)
        u = jnp.asarray(r.normal(size=(B, S, D)), jnp.float32)
        got = rglru_scan_ref(u, log_a)
        a = np.exp(np.asarray(log_a))
        un = np.asarray(u)
        h = np.zeros((B, D))
        want = np.zeros((B, S, D))
        for t in range(S):
            h = a[:, t] * h + un[:, t]
            want[:, t] = h
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    def test_initial_state_fold(self):
        r = RNG(1)
        B, S, D = 1, 9, 4
        log_a = jnp.asarray(-r.uniform(0.01, 1.0, (B, S, D)), jnp.float32)
        u = jnp.asarray(r.normal(size=(B, S, D)), jnp.float32)
        h0 = jnp.asarray(r.normal(size=(B, D)), jnp.float32)
        full = rglru_scan_ref(jnp.concatenate([h0[:, None], u], 1),
                              jnp.concatenate([jnp.zeros((B, 1, D)), log_a], 1))
        got = rglru_scan_ref(u, log_a, h0=h0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, 1:]),
                                   rtol=1e-5, atol=1e-5)


class TestChunkedAttention:
    # (S, causal, window) at q_block 16, kv_block 8: every causal or
    # windowed case has kv blocks dead for a whole q block, which the skip
    # drops; the S=40 cases keep the ids they had before S=64 joined them
    GRID = [pytest.param(40, c, w, id=f"{c}-{w}")
            for c, w in [(True, 0), (True, 7), (False, 0)]] + \
        [pytest.param(64, c, w, id=f"S64-{c}-{w}")
         for c, w in [(True, 0), (True, 7), (True, 20), (False, 0)]]

    @staticmethod
    def _qkv(seed, B, S, T, H, Kv, dh):
        r = RNG(seed)
        return (jnp.asarray(r.normal(size=(B, S, H, dh)), jnp.float32),
                jnp.asarray(r.normal(size=(B, T, Kv, dh)), jnp.float32),
                jnp.asarray(r.normal(size=(B, T, Kv, dh)), jnp.float32))

    @pytest.mark.parametrize("S,causal,window", GRID)
    def test_matches_naive(self, S, causal, window):
        q, k, v = self._qkv(5, 2, S, S, 4, 2, 8)
        naive = attention(q, k, v, causal=causal, window=window,
                          impl="xla_naive")
        chunked = attention(q, k, v, causal=causal, window=window,
                            impl="xla_chunked", q_block=16, kv_block=8)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(naive),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("S,causal,window", GRID)
    def test_grad_matches_naive(self, S, causal, window):
        """The skipped blocks' backward pass: gradients of q, k and v."""
        q, k, v = self._qkv(7, 2, S, S, 4, 2, 8)
        w = jnp.asarray(RNG(8).normal(size=q.shape), jnp.float32)

        def loss(impl):
            return lambda q, k, v: (attention(
                q, k, v, causal=causal, window=window, impl=impl,
                q_block=16, kv_block=8) * w).sum()

        naive = jax.grad(loss("xla_naive"), argnums=(0, 1, 2))(q, k, v)
        chunked = jax.grad(loss("xla_chunked"), argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(chunked, naive):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_softcap_and_offset(self):
        q, k, v = self._qkv(6, 1, 24, 48, 2, 2, 8)
        naive = attention(q, k, v, causal=True, softcap=20.0, q_offset=24,
                          impl="xla_naive")
        chunked = attention(q, k, v, causal=True, softcap=20.0, q_offset=24,
                            impl="xla_chunked", q_block=8, kv_block=16)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(naive),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [0, 7, 19])
    def test_traced_offset(self, window):
        """q_offset traced under jit (as training passes positions[0]),
        Sq != Sk: the skip decides on device; value and gradient.  At
        offset 17 a q block's last query is a kv block's first key, and
        window 19 reaches exactly the last key of the block before a q
        block's window: each edge keeps a block with one live entry."""
        q, k, v = self._qkv(9, 1, 40, 64, 4, 2, 8)

        def loss(impl):
            def f(q, k, v, off):
                return (attention(q, k, v, causal=True, window=window,
                                  q_offset=off, impl=impl, q_block=16,
                                  kv_block=8) ** 2).sum()
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

        off = jnp.asarray(17, jnp.int32)
        (vn, gn), (vc, gc) = (loss("xla_naive")(q, k, v, off),
                              loss("xla_chunked")(q, k, v, off))
        np.testing.assert_allclose(float(vc), float(vn), rtol=2e-5)
        for got, want in zip(gc, gn):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("S,causal,window", GRID)
    def test_live_kv_blocks_counts_unmasked_blocks(self, S, causal, window):
        """live_kv_blocks equals a brute-force count of the (q, kv) block
        pairs in which the naive mask leaves any entry."""
        bias = np.asarray(_mask_bias(jnp.arange(S), jnp.arange(S), causal,
                                     window))
        nq, nk = -(-S // 16), -(-S // 8)
        want = sum(bool((bias[i * 16:(i + 1) * 16,
                              j * 8:(j + 1) * 8] == 0).any())
                   for i in range(nq) for j in range(nk))
        assert live_kv_blocks(S, S, 16, 8, causal, window) == want

    def test_live_kv_blocks_mellum_period(self):
        """Mellum at 4096: three 1024-window layers and one full causal
        layer compute 62 of their 128 (q, kv) block pairs."""
        live = [live_kv_blocks(4096, 4096, 512, 1024, True, w)
                for w in (1024, 1024, 1024, 0)]
        assert live == [14, 14, 14, 20]
        assert sum(live) == 62


@pytest.mark.slow  # dense-oracle comparisons: full MoE forwards
class TestMoE:
    def _cfg(self, E=4, K=2, cf=8.0, shared=0):
        return ArchConfig(
            name="t", family="moe", n_layers=2, d_model=16, n_heads=2,
            n_kv=2, d_head=8, d_ff=32, vocab=64,
            moe=MoEConfig(num_experts=E, top_k=K, d_ff_expert=24,
                          capacity_factor=cf, num_shared=shared),
            compute_dtype="float32")

    def test_gather_matches_dense_oracle(self):
        cfg = self._cfg()
        specs = moe_lib.moe_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(0))
        x = jnp.asarray(RNG(7).normal(size=(2, 6, 16)), jnp.float32)
        y_fast, aux_fast = moe_lib.moe_apply(params, x, cfg)
        y_ref, aux_ref = moe_lib.moe_apply_dense(params, x, cfg)
        np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(aux_fast.aux),
                                   np.asarray(aux_ref.aux), rtol=1e-5)

    def test_shared_experts(self):
        cfg = self._cfg(shared=1)
        specs = moe_lib.moe_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(1))
        x = jnp.asarray(RNG(8).normal(size=(1, 5, 16)), jnp.float32)
        y_fast, _ = moe_lib.moe_apply(params, x, cfg)
        y_ref, _ = moe_lib.moe_apply_dense(params, x, cfg)
        np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_drops_tokens(self):
        """With tiny capacity the outputs differ from the oracle (tokens
        dropped) but stay finite — the documented overflow behavior."""
        cfg = self._cfg(cf=0.25)
        specs = moe_lib.moe_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(2))
        x = jnp.asarray(RNG(9).normal(size=(2, 8, 16)), jnp.float32)
        y, aux = moe_lib.moe_apply(params, x, cfg)
        assert np.isfinite(np.asarray(y)).all()

    def test_aux_loss_uniform_router_is_one(self):
        """Balanced routing gives aux ~= 1 (E * sum_e (1/E)*(1/E) * E)."""
        cfg = self._cfg(E=8, K=2)
        specs = moe_lib.moe_specs(cfg)
        params = init_params(specs, jax.random.PRNGKey(3))
        params = dict(params)
        params["router"] = jnp.zeros_like(params["router"])  # uniform
        x = jnp.asarray(RNG(10).normal(size=(4, 16, 16)), jnp.float32)
        _, aux = moe_lib.moe_apply(params, x, cfg)
        assert aux.aux.shape == (4,)
        assert np.all((0.9 <= np.asarray(aux.aux))
                      & (np.asarray(aux.aux) <= 1.1))
