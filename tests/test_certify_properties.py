"""Property tests for the theory-certification layer (PR 10).

Runs under real hypothesis when installed, else the vendored stub in
tests/_stubs (deterministic per-test seeds, no shrinking — see
tests/conftest.py).  Sizes are kept small: every example computes an
SVD or an eigendecomposition.
"""

import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import codes as C
from repro.core import registry
from repro.core import theory as T
from repro.core.certify import adversarial_err1_bound, certify

EXAMPLES = settings(max_examples=40, deadline=None)


# --------------------------------------------------------------------------
# fundamental lower bound: monotonicity + normalization
# --------------------------------------------------------------------------


class TestFundamentalBoundProperties:
    @EXAMPLES
    @given(k=st.integers(4, 64), s=st.integers(1, 12), r=st.integers(0, 64))
    def test_normalized_to_unit_interval(self, k, s, r):
        r = min(r, k)
        s = min(s, k)
        lb = T.fundamental_err_lower_bound(k, s, r)
        assert 0.0 <= lb / k <= 1.0

    @EXAMPLES
    @given(k=st.integers(4, 64), s=st.integers(1, 11), r=st.integers(1, 64))
    def test_non_increasing_in_s(self, k, s, r):
        r = min(r, k)
        s = min(s, k - 1)
        assert (T.fundamental_err_lower_bound(k, s + 1, r)
                <= T.fundamental_err_lower_bound(k, s, r) + 1e-12)

    @EXAMPLES
    @given(k=st.integers(4, 64), s=st.integers(1, 12), r=st.integers(1, 63))
    def test_non_increasing_in_survivors(self, k, s, r):
        # NOTE on conventions: this repo's r counts SURVIVORS, so the
        # bound is non-increasing in r; papers whose r counts stragglers
        # state the same monotonicity as "non-decreasing in r"
        r = min(r, k - 1)
        s = min(s, k)
        assert (T.fundamental_err_lower_bound(k, s, r + 1)
                <= T.fundamental_err_lower_bound(k, s, r) + 1e-12)

    @EXAMPLES
    @given(k=st.integers(4, 64), s=st.integers(1, 12),
           delta=st.floats(0.0, 1.0))
    def test_load_form_unit_interval_and_monotone_in_delta(self, k, s,
                                                           delta):
        s = min(s, k)
        lb = T.fundamental_err_lower_bound_load(k, s, delta)
        assert 0.0 <= lb / k <= 1.0
        d2 = min(1.0, delta + 0.1)
        assert (T.fundamental_err_lower_bound_load(k, s, d2)
                >= lb - 1e-12)


# --------------------------------------------------------------------------
# spectral certificates
# --------------------------------------------------------------------------


class TestCertificateProperties:
    @EXAMPLES
    @given(k=st.integers(8, 48), s=st.integers(2, 6),
           delta=st.floats(0.0, 0.9))
    def test_bound_monotone_in_delta_and_s(self, k, s, delta):
        lam = 2.0 * math.sqrt(s)
        b = adversarial_err1_bound(k, k, s, delta, lam)
        assert b >= 0.0
        assert (adversarial_err1_bound(k, k, s, min(delta + 0.05, 0.9), lam)
                >= b - 1e-12)
        assert adversarial_err1_bound(k, k, s + 1, delta, lam) <= b + 1e-12

    @EXAMPLES
    @given(k=st.integers(8, 40), mult=st.integers(1, 3),
           s=st.integers(2, 5), seed=st.integers(0, 10**6))
    def test_err_frac_bound_normalized(self, k, mult, s, seed):
        n = k * mult
        code = registry.make("expander", k=k, n=n, s=min(s, k - 1),
                             seed=seed)
        cert = certify(code)
        for delta in (0.0, 0.2, 0.5):
            assert 0.0 <= cert.err_frac_bound(delta) <= 1.0

    @EXAMPLES
    @given(k=st.integers(6, 32), s=st.integers(2, 5),
           seed=st.integers(0, 10**6))
    def test_bipartite_gap_agrees_with_symmetric_square(self, k, s, seed):
        """sigma_2(G) == second-largest singular value read off the dense
        symmetric square [[0, G], [G^T, 0]]: its |eigenvalues| are each
        sigma_i twice (plus |k - n| zeros), so the 3rd largest is
        sigma_2 — the bipartite spectral_gap must match it."""
        n = max(4, k - (k % 2) - 2)  # ragged: n != k
        code = registry.make("expander", k=k, n=n, s=min(s, k - 1),
                             seed=seed)
        gap = C.spectral_gap(code)
        G = code.G.astype(np.float64)
        B = np.block([[np.zeros((k, k)), G],
                      [G.T, np.zeros((n, n))]])
        ev = np.sort(np.abs(np.linalg.eigvalsh(B)))[::-1]
        assert gap == pytest.approx(float(ev[2]), abs=1e-8)

    @EXAMPLES
    @given(k=st.integers(6, 32), s=st.integers(2, 5),
           seed=st.integers(0, 10**6))
    def test_square_symmetric_path_equals_svd_path(self, k, s, seed):
        """For symmetric nonnegative G the legacy eig formula
        max(|lambda_2|, |lambda_k|) IS sigma_2 — the two spectral_gap
        branches agree on sregular codes."""
        if (k * s) % 2:
            k += 1
        s = min(s, k - 1)
        code = registry.make("sregular", k=k, n=k, s=s, seed=seed)
        gap = C.spectral_gap(code)
        sig = np.linalg.svd(code.G.astype(np.float64), compute_uv=False)
        assert gap == pytest.approx(float(sig[1]), abs=1e-8)


# --------------------------------------------------------------------------
# legal_s floor consistency (registry + fundamental limit)
# --------------------------------------------------------------------------


class TestLegalSFloor:
    @EXAMPLES
    @given(family=st.sampled_from(("bgc", "expander", "sregular", "frc")),
           k=st.integers(16, 64), delta=st.floats(0.1, 0.5),
           budget=st.floats(0.01, 0.2))
    def test_make_succeeds_at_floor_and_raises_below(self, family, k,
                                                     delta, budget):
        fam = registry.get(family)
        try:
            floor = fam.s_floor(k, k, delta=delta, error_budget=budget)
        except ValueError:
            return  # budget infeasible at every legal s: nothing to check
        # at the floor: construction succeeds under the budget contract
        code = fam.make(k, k, floor, seed=0, delta=delta,
                        error_budget=budget)
        assert code.s >= 1
        # below the floor: every legal rung must raise, actionably
        below = [x for x in fam.legal_s(k, k, hi=floor - 1)]
        for s_bad in below[-2:]:
            with pytest.raises(ValueError, match="fundamental-limit floor"):
                fam.make(k, k, s_bad, seed=0, delta=delta,
                         error_budget=budget)

    @EXAMPLES
    @given(k=st.integers(16, 64), delta=st.floats(0.1, 0.5),
           budget=st.floats(0.01, 0.2))
    def test_floor_is_minimal(self, k, delta, budget):
        fam = registry.get("bgc")
        try:
            floor = fam.s_floor(k, k, delta=delta, error_budget=budget)
        except ValueError:
            return
        feasible = fam.legal_s(k, k, delta=delta, error_budget=budget)
        assert feasible and feasible[0] == floor
        # nothing below the floor is feasible
        assert all(s >= floor for s in feasible)

    def test_infeasible_budget_raises_actionably(self):
        fam = registry.get("bgc")
        with pytest.raises(ValueError, match="raise the error budget"):
            fam.s_floor(32, 32, delta=1.0, error_budget=0.5)

    def test_budget_without_delta_raises(self):
        fam = registry.get("bgc")
        with pytest.raises(ValueError, match="requires delta"):
            fam.make(32, 32, 4, seed=0, error_budget=0.1)
        with pytest.raises(ValueError, match="requires delta"):
            fam.legal_s(32, 32, error_budget=0.1)
