"""DecodeEngine: batched-vs-scalar decoder equivalence (property tests),
batched Pallas kernels in interpret mode, the mask->weights LRU cache,
and the batched Monte-Carlo path."""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core import codes as C
from repro.core import decoding as D
from repro.core import simulate as S
from repro.core.engine import DecodeEngine
from repro.kernels import ops


def _code(scheme, k, s, seed):
    rng = np.random.default_rng(seed)
    if scheme == "frc":
        while k % s:
            s -= 1
        return C.frc(k, k, max(s, 1), rng=rng)
    return C.make_code(scheme, k=k, n=k, s=s, rng=rng)


def _masks(n, B, seed, frac=0.7):
    rng = np.random.default_rng(seed)
    return rng.random((B, n)) < frac


# ------------------- batched == scalar, per decoder -------------------------

@settings(max_examples=25, deadline=None)
@given(k=st.integers(12, 64), s=st.integers(1, 6),
       scheme=st.sampled_from(["frc", "bgc", "rbgc", "cyclic"]),
       seed=st.integers(0, 10_000))
def test_property_batched_matches_scalar(k, s, scheme, seed):
    """For random codes and masks, DecodeEngine batched weights match
    the scalar decoding.* oracles per mask (the ISSUE acceptance
    property), and errors match the scalar error definitions."""
    code = _code(scheme, k, s, seed)
    masks = _masks(code.n, 9, seed + 1)
    # pinv: the scalar-oracle-equivalent path (the gram default agrees
    # on errors but not on weights at rank-deficient supports)
    eng = DecodeEngine(code, iters=5, optimal_impl="pinv")

    one = eng.decode_batch(masks, "onestep")
    opt = eng.decode_batch(masks, "optimal")
    alg = eng.decode_batch(masks, "algorithmic")
    s_eff = max(1, int(round((code.G != 0).sum() / code.n)))
    for b, m in enumerate(masks):
        assert_allclose(one.weights[b], D.onestep_weights(code.G, m),
                        atol=1e-10)
        r = int(m.sum())
        assert_allclose(one.errors[b],
                        D.err1(code.G[:, m], D.default_rho(code.k, r, s_eff)),
                        atol=1e-8, rtol=1e-8)
        assert_allclose(opt.weights[b], D.optimal_weights(code.G, m),
                        atol=1e-6)
        assert_allclose(alg.weights[b],
                        D.algorithmic_weights(code.G, m, iters=5),
                        atol=1e-8)


def test_algorithmic_all_zero_support_decodes_to_zero():
    """Alive workers that hold no task: the scalar decoders give w = 0
    and err = k, as the batched path does (no 0/0 from nu = 0)."""
    G = np.zeros((4, 6))
    G[:, :2] = 1.0
    m = np.array([False, False, True, True, True, True])
    w = D.algorithmic_weights(G, m, iters=3)
    assert_allclose(w, np.zeros(6))
    assert_allclose(w, D.algorithmic_weights_batch(G, m[None], 3)[0])
    assert_allclose(D.algorithmic_error_curve(G[:, m], 3), np.full(4, 4.0))


def test_batched_optimal_error_matches_lstsq():
    code = _code("bgc", 48, 5, 3)
    masks = _masks(48, 12, 4)
    res = DecodeEngine(code, optimal_impl="pinv").decode_batch(
        masks, "optimal")
    for b, m in enumerate(masks):
        assert_allclose(res.errors[b], D.err(code.G[:, m]),
                        atol=1e-7, rtol=1e-6)
    # the gram DEFAULT lands on the same least-squares errors to its
    # ridge floor (the weights may differ on rank-deficient supports)
    dflt = DecodeEngine(code).decode_batch(masks, "optimal")
    assert_allclose(dflt.errors, res.errors, atol=1e-4, rtol=1e-4)


def test_degenerate_masks():
    code = _code("bgc", 24, 3, 0)
    masks = np.zeros((3, 24), bool)        # every worker straggles
    for method in ("onestep", "optimal", "algorithmic", "ignore"):
        res = DecodeEngine(code).decode_batch(masks, method)
        assert np.all(res.weights == 0) or method == "ignore"
        assert res.weights.shape == (3, 24)
        assert np.all(np.isfinite(res.errors))


def test_unknown_method_raises():
    code = _code("bgc", 16, 3, 0)
    with pytest.raises(ValueError):
        DecodeEngine(code).decode_batch(np.ones((1, 16), bool), "nope")


# ------------------- ELL packing ---------------------------------------------

@settings(max_examples=20, deadline=None)
@given(k=st.integers(8, 60), s=st.integers(1, 6),
       scheme=st.sampled_from(["frc", "bgc", "rbgc", "cyclic"]),
       seed=st.integers(0, 10_000))
def test_property_ell_roundtrip(k, s, scheme, seed):
    """The row-ELL packing reconstructs G exactly (padding adds 0)."""
    code = _code(scheme, k, s, seed)
    idx, val = code.ell()
    assert idx.shape == val.shape and idx.shape[0] == code.k
    G2 = np.zeros_like(code.G)
    for i in range(code.k):
        np.add.at(G2[i], idx[i], val[i])
    assert_allclose(G2, code.G)
    # cached: second call returns the identical objects
    assert code.ell()[0] is idx


# ------------------- batched Pallas kernels (interpret) ----------------------

@pytest.mark.parametrize("k,n,s,B", [(100, 100, 10, 7), (130, 70, 5, 9),
                                     (64, 64, 4, 33)])
def test_batched_onestep_kernel_matches_ref(k, n, s, B):
    rng = np.random.default_rng(0)
    G = (rng.random((k, n)) < s / k).astype(np.float32)
    masks = rng.random((B, n)) < 0.7
    rhos = (rng.random(B) + 0.5).astype(np.float32)
    want = np.asarray(ops.batched_onestep_decode(
        jnp.asarray(G), jnp.asarray(masks), jnp.asarray(rhos), impl="xla"))
    got = np.asarray(ops.batched_onestep_decode(
        jnp.asarray(G), jnp.asarray(masks), jnp.asarray(rhos),
        impl="pallas_interpret", bb=16, bk=64, bn=64))
    assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_batched_onestep_ell_kernel_matches_dense():
    rng = np.random.default_rng(1)
    code = C.bgc(k=96, n=96, s=6, rng=rng)
    masks = rng.random((11, 96)) < 0.75
    rhos = (rng.random(11) + 0.5).astype(np.float32)
    idx, val = code.ell()
    dense = np.asarray(ops.batched_onestep_decode(
        jnp.asarray(code.G.astype(np.float32)), jnp.asarray(masks),
        jnp.asarray(rhos), impl="pallas_interpret", bb=8, bk=32, bn=32))
    ell = np.asarray(ops.batched_onestep_decode_ell(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(masks),
        jnp.asarray(rhos), impl="pallas_interpret", bb=8, bk=32))
    assert_allclose(ell, dense, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("k,n,s,iters", [(100, 100, 10, 4), (130, 70, 5, 2)])
def test_batched_algorithmic_kernel_matches_scalar_kernel(k, n, s, iters):
    """Each batch row of the batched kernel equals the scalar kernel run
    on that mask, and the returned weights match the numpy batch path."""
    rng = np.random.default_rng(2)
    G = (rng.random((k, n)) < s / k).astype(np.float32)
    masks = rng.random((6, n)) < 0.7
    nus = D.spectral_norm_sq_batch(G, masks).astype(np.float32) * 1.01
    U, X = ops.batched_algorithmic_decode(
        jnp.asarray(G), jnp.asarray(masks), jnp.asarray(nus), iters,
        impl="pallas_interpret", bb=8, bk=64, bn=64, return_weights=True)
    U, X = np.asarray(U), np.asarray(X)
    for b in range(masks.shape[0]):
        u1 = np.asarray(ops.algorithmic_decode(
            jnp.asarray(G), jnp.asarray(masks[b]), float(nus[b]), iters,
            impl="pallas_interpret", bk=64, bn=64))
        assert_allclose(U[b], u1, atol=1e-4, rtol=1e-4)
    W_np = D.algorithmic_weights_batch(G.astype(np.float64), masks, iters,
                                       nu=nus.astype(np.float64))
    assert_allclose(X * masks, W_np, atol=1e-4, rtol=1e-3)


def test_engine_pallas_interpret_backend_matches_numpy():
    code = C.bgc(k=64, n=64, s=5, rng=np.random.default_rng(3))
    masks = _masks(64, 10, 5)
    res_np = DecodeEngine(code, backend="numpy").decode_batch(masks)
    for sparse in ("always", "never"):
        res_k = DecodeEngine(code, backend="pallas_interpret",
                             sparse=sparse).decode_batch(masks)
        assert_allclose(res_k.weights, res_np.weights, atol=1e-5)
        assert_allclose(res_k.errors, res_np.errors, atol=1e-3, rtol=1e-4)


# ------------------- LRU cache -----------------------------------------------

def test_decode_cache_hits_on_repeated_masks():
    code = C.bgc(k=32, n=32, s=4, rng=np.random.default_rng(7))
    eng = DecodeEngine(code, cache_size=8)
    mask = np.ones(32, bool)
    mask[[3, 7]] = False
    w1 = eng.decode(mask)
    w2 = eng.decode(mask)
    assert w1 is w2                      # memoized object
    assert eng.cache_info()["hits"] == 1
    assert eng.cache_info()["misses"] == 1
    assert_allclose(w1, D.onestep_weights(code.G, mask), atol=1e-12)
    # different method -> distinct entry
    eng.decode(mask, method="optimal")
    assert eng.cache_info()["misses"] == 2


def test_decode_cache_evicts_lru():
    code = C.bgc(k=16, n=16, s=3, rng=np.random.default_rng(8))
    eng = DecodeEngine(code, cache_size=2)
    rng = np.random.default_rng(9)
    m = [rng.random(16) < 0.7 for _ in range(3)]
    eng.decode(m[0]); eng.decode(m[1]); eng.decode(m[2])  # evicts m[0]
    assert eng.cache_info()["size"] == 2
    eng.decode(m[0])
    assert eng.cache_info()["misses"] == 4  # m[0] was evicted -> re-decoded


def test_cached_weights_are_immutable():
    code = C.bgc(k=16, n=16, s=3, rng=np.random.default_rng(10))
    eng = DecodeEngine(code)
    w = eng.decode(np.ones(16, bool))
    with pytest.raises(ValueError):
        w[0] = 99.0


# ------------------- trainer integration ------------------------------------

def test_trainer_decode_weights_cached_and_renormed():
    pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import build_model
    from repro.training import CodedTrainConfig, CodedTrainer

    model = build_model(get_config("minicpm-2b", smoke=True))
    tr = CodedTrainer(model, CodedTrainConfig(code="frc", n_workers=8, s=2,
                                              decoder="onestep", seq_len=16))
    mask = np.ones(8, bool)
    mask[[1, 5]] = False
    w1 = tr.decode_weights_for(mask)
    w2 = tr.decode_weights_for(mask)
    assert_allclose(w1, w2)
    assert tr.engine.cache_info()["hits"] >= 1
    # renorm invariant: sum(G @ w) == k
    assert abs(float((tr.code.G @ w1).sum()) - tr.code.k) < 1e-6


# ------------------- batched Monte-Carlo path --------------------------------

def test_simulate_batched_matches_manual_loop():
    """monte_carlo_error's batched cell equals a hand-rolled loop over
    the same masks/codes (same rng stream => identical draws)."""
    k, s, delta, trials = 40, 4, 0.25, 64
    res = S.monte_carlo_error("frc", k=k, n=k, s=s, delta=delta,
                              trials=trials, decoder="onestep", seed=11)
    rng = np.random.default_rng(11)
    code = C.make_code("frc", k=k, n=k, s=s, rng=rng)
    masks = S.sample_straggler_masks(k, int(round(delta * k)), trials, rng)
    errs = np.array([D.err1(code.G[:, m],
                            D.default_rho(k, int(m.sum()), s))
                     for m in masks]) / k
    assert res.mean == pytest.approx(float(errs.mean()), abs=1e-12)
    assert res.p_zero == pytest.approx(float((errs < 1e-9).mean()))


def test_sample_straggler_masks_counts_and_determinism():
    masks = S.sample_straggler_masks(30, 7, 100, np.random.default_rng(0))
    assert masks.shape == (100, 30)
    assert np.all((~masks).sum(axis=1) == 7)
    again = S.sample_straggler_masks(30, 7, 100, np.random.default_rng(0))
    assert np.array_equal(masks, again)
