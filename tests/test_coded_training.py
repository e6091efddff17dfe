"""Integration tests: coded training loop, fused-vs-master-decode
equivalence, checkpoint/restart, elasticity, compression."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as CFG
from repro.models import build_model
from repro.optim import OptConfig
from repro.runtime import (FaultInjector, FaultPlan,
                           FixedFractionStragglers)
from repro.training import (CodedTrainConfig, CodedTrainer,
                            explicit_master_decode_grads)

pytestmark = pytest.mark.slow  # training e2e: jit + multi-step loops


def tiny_model():
    cfg = CFG.get_config("minicpm-2b", smoke=True)
    return build_model(cfg)


def make_trainer(model, straggler=None, faults=None, **kw):
    defaults = dict(code="frc", n_workers=8, s=2, decoder="onestep",
                    rows_per_slot=1, seq_len=16, steps=6, seed=0,
                    opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
                    log_every=1)
    defaults.update(kw)
    return CodedTrainer(model, CodedTrainConfig(**defaults),
                        straggler_model=straggler, fault_injector=faults)


class TestFusedDecodeEquivalence:
    """docs/architecture.md §2.1: loss-reweighted all-reduce == explicit master decode."""

    @pytest.mark.parametrize("code,decoder", [
        ("frc", "onestep"), ("bgc", "onestep"),
        ("frc", "optimal"), ("bgc", "optimal"),
    ])
    def test_grads_identical(self, code, decoder):
        model = tiny_model()
        tr = make_trainer(model, code=code, decoder=decoder,
                          exact_decode_renorm=False)
        params = model.init(jax.random.PRNGKey(0))
        mask = np.ones(8, dtype=bool)
        mask[[1, 5]] = False
        # explicit: per-worker partials, decoded on the 'master'
        explicit, w = explicit_master_decode_grads(model, params, tr, 0, mask)
        # fused: one loss-reweighted grad
        batch_np = tr.pipeline.batch_for_step(0, w)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        grads = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
        fused = jnp.concatenate([g.reshape(-1).astype(jnp.float32)
                                 for g in jax.tree_util.tree_leaves(grads)])
        np.testing.assert_allclose(np.asarray(fused), np.asarray(explicit),
                                   rtol=5e-4, atol=5e-6)

    def test_no_stragglers_equals_uncoded_gradient(self):
        """With zero stragglers and an exact-decoding code, the coded
        gradient equals the plain uncoded gradient over unique data."""
        model = tiny_model()
        # pinv: the exact-oracle opt-in — the gram default's ridge floor
        # perturbs G@w at the ~1e-7 scale this test pins
        tr = make_trainer(model, code="frc", decoder="optimal",
                          exact_decode_renorm=False, optimal_impl="pinv")
        params = model.init(jax.random.PRNGKey(1))
        mask = np.ones(8, dtype=bool)
        w = tr.decode_weights_for(mask)
        v = tr.code.G @ w
        np.testing.assert_allclose(v, 1.0, atol=1e-7)  # exact decode
        coded_np = tr.pipeline.batch_for_step(0, w)
        uncoded_np = tr.pipeline.uncoded_batch_for_step(0)
        g_coded = jax.grad(lambda p: model.loss_fn(
            p, {k: jnp.asarray(x) for k, x in coded_np.items()})[0])(params)
        g_ref = jax.grad(lambda p: model.loss_fn(
            p, {k: jnp.asarray(x) for k, x in uncoded_np.items()})[0])(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_coded),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-4, atol=5e-6)


def _flat_grad(model, params, batch_np):
    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
    loss, grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch)[0])(params)
    return float(loss), jnp.concatenate(
        [g.reshape(-1).astype(jnp.float32)
         for g in jax.tree_util.tree_leaves(grads)])


def _held_examples(asg, T):
    ids = asg.task_ids
    return np.unique(ids[ids >= 0]).size * T


class TestFold:
    """data.pipeline.unique_batch_for_step: the physical batch folded to
    each held example once, weighted by its replicas' summed weight."""

    @pytest.mark.parametrize("code,decoder", [
        ("frc", "onestep"), ("bgc", "onestep"),
        ("frc", "optimal"), ("bgc", "optimal"),
    ])
    def test_folded_batch_matches_physical_and_master_decode(self, code,
                                                            decoder):
        model = tiny_model()
        tr = make_trainer(model, code=code, decoder=decoder, rows_per_slot=2,
                          exact_decode_renorm=False)
        params = model.init(jax.random.PRNGKey(0))
        mask = np.ones(8, dtype=bool)
        mask[[1, 5]] = False
        explicit, w = explicit_master_decode_grads(model, params, tr, 0, mask)
        phys_np = tr.pipeline.batch_for_step(0, w)
        fold_np = tr.pipeline.unique_batch_for_step(0, w)
        asg = tr.assignment
        assert fold_np["tokens"].shape[0] == _held_examples(asg, 2)
        assert fold_np["tokens"].shape[0] < phys_np["tokens"].shape[0]
        # each held task once, in task order, weighted (G w)_i / (k T)
        held = np.unique(asg.task_ids[asg.task_ids >= 0])
        want_w = np.repeat((asg.G @ w)[held] / (asg.k * 2), 2)
        np.testing.assert_allclose(fold_np["loss_weight"], want_w,
                                   rtol=1e-12, atol=0)
        loss_p, g_p = _flat_grad(model, params, phys_np)
        loss_f, g_f = _flat_grad(model, params, fold_np)
        np.testing.assert_allclose(loss_f, loss_p, rtol=5e-4, atol=5e-6)
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_p),
                                   rtol=5e-4, atol=5e-6)
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(explicit),
                                   rtol=5e-4, atol=5e-6)

    def test_fold_of_uncoded_is_identity(self):
        model = tiny_model()
        tr = make_trainer(model, code="uncoded", s=1, rows_per_slot=2)
        w = tr.decode_weights_for(np.ones(8, dtype=bool))
        phys = tr.pipeline.batch_for_step(3, w)
        fold = tr.pipeline.unique_batch_for_step(3, w)
        assert set(fold) == set(phys)
        for key in phys:
            np.testing.assert_array_equal(fold[key], phys[key])

    def test_history_rows_count_what_the_step_computed(self):
        model = tiny_model()
        fused = make_trainer(model, code="bgc", steps=2, rows_per_slot=2)
        rows = [h["rows"] for h in fused.run()["history"]]
        held = _held_examples(fused.assignment, 2)
        assert held < fused.pipeline.physical_batch
        assert rows == [held] * 2
        dist = make_trainer(model, code="bgc", steps=2, rows_per_slot=2,
                            dist_mode="coded_allreduce")
        rows = [h["rows"] for h in dist.run()["history"]]
        assert rows == [dist.pipeline.physical_batch] * 2

    def test_set_s_recode_rebuilds_the_fold(self):
        from repro.control.policy import Action

        model = tiny_model()
        tr = make_trainer(model, code="bgc", steps=1, rows_per_slot=2)
        before = tr.pipeline.unique_batch_for_step(0, np.ones(8))
        tr._apply_action(Action(kind="set_s", value=4))
        asg = tr.assignment
        assert asg.slots >= 4
        w = tr.decode_weights_for(np.ones(8, dtype=bool))
        fold = tr.pipeline.unique_batch_for_step(0, w)
        held = np.unique(asg.task_ids[asg.task_ids >= 0])
        assert fold["tokens"].shape[0] == held.size * 2 \
            != before["tokens"].shape[0]
        np.testing.assert_allclose(
            fold["loss_weight"],
            np.repeat((asg.G @ w)[held] / (asg.k * 2), 2), rtol=1e-12)
        # the held rows are the physical rows of the new layout
        phys = tr.pipeline.batch_for_step(0, w)
        first = {}
        for r, u in enumerate(asg.unique_row_of_slot(2)):
            first.setdefault(int(u), r)
        src = [first[u] for u in sorted(first) if u >= 0]
        np.testing.assert_array_equal(fold["tokens"], phys["tokens"][src])
        out = tr.run(steps=1)
        assert out["history"][-1]["rows"] == held.size * 2


class TestTrainerLoop:
    def test_loss_decreases_no_stragglers(self):
        model = tiny_model()
        tr = make_trainer(model, steps=16, code="uncoded", s=1)
        out = tr.run()
        losses = [h["mean_ce"] for h in out["history"]]
        assert losses[-1] < losses[0], f"no learning: {losses}"
        assert all(np.isfinite(l) for l in losses)

    def test_coded_training_with_stragglers_learns(self):
        model = tiny_model()
        tr = make_trainer(model, steps=16, code="frc", s=2,
                          straggler=FixedFractionStragglers(0.25, seed=3))
        out = tr.run()
        losses = [h["mean_ce"] for h in out["history"]]
        assert losses[-1] < losses[0]
        assert any(h["stragglers"] > 0 for h in out["history"])

    def test_decode_error_logged_matches_theory_scale(self):
        model = tiny_model()
        tr = make_trainer(model, steps=4, code="frc", s=2,
                          straggler=FixedFractionStragglers(0.25, seed=5))
        out = tr.run()
        errs = [h["decode_err"] for h in out["history"]]
        assert all(0 <= e <= 1 for e in errs)


class TestStalenessPipelining:
    """docs/architecture.md §10: stale-weighted decode overlap."""

    def test_staleness_zero_weight_stream_bitwise_synchronous(self):
        """staleness=0 IS the synchronous mode — the applied per-step
        weight stream matches the default trainer bit for bit."""
        model = tiny_model()
        a = make_trainer(model, steps=5, code="bgc",
                         straggler=FixedFractionStragglers(0.25, seed=3))
        a.run()
        b = make_trainer(model, steps=5, code="bgc", staleness=0,
                         straggler=FixedFractionStragglers(0.25, seed=3))
        b.run()
        assert len(a.weight_log) == len(b.weight_log) == 5
        for wa, wb in zip(a.weight_log, b.weight_log):
            np.testing.assert_array_equal(wa, wb)

    def test_staleness_one_applies_previous_steps_weights(self):
        """Step t applies the decode of step t-1's mask re-masked by
        step t's stragglers; step 0 warm-starts from all-alive."""
        model = tiny_model()
        tr = make_trainer(model, steps=5, code="bgc", staleness=1,
                          straggler=FixedFractionStragglers(0.25, seed=5))
        tr.run()
        ref = make_trainer(model, code="bgc")      # same seed -> same code
        np.testing.assert_array_equal(ref.code.G, tr.code.G)
        sampler = FixedFractionStragglers(0.25, seed=5)
        masks = [sampler.sample(t, 8) for t in range(5)]
        for t in range(5):
            prev = np.ones(8, bool) if t == 0 else masks[t - 1]
            want = ref.decode_weights_for(prev) * masks[t]
            np.testing.assert_array_equal(tr.weight_log[t], want)

    def test_staleness_flush_on_recode_and_set_decoder(self):
        """Elastic re-codes and decoder switches drop in-flight stale
        weights; the next step warm-starts against the NEW code."""
        from repro.control.policy import Action

        model = tiny_model()
        strag = FixedFractionStragglers(0.25, seed=7)
        tr = make_trainer(model, steps=2, code="bgc", staleness=1,
                          straggler=strag)
        out = tr.run()
        assert tr._pending_w is not None and len(tr._pending_w) == 1
        tr._apply_action(Action(kind="set_decoder", value="onestep"))
        assert tr._pending_w is None               # decoder switch flushes
        tr._build_code(6)                          # elastic re-code path
        tr._step_fn = tr._make_step_fn()
        assert tr._pending_w is None               # rebuild flushes too
        out = tr.run(state=out["state"], start_step=2, steps=1)
        # step 2 warm-started: all-alive decode of the NEW 6-worker code
        m2 = strag.sample(2, 6)
        want = tr.decode_weights_for(np.ones(6, bool)) * m2
        np.testing.assert_array_equal(tr.weight_log[2], want)
        assert all(np.isfinite(h["mean_ce"]) for h in tr.history)

    def test_staleness_validation(self):
        model = tiny_model()
        with pytest.raises(ValueError):
            make_trainer(model, staleness=-1)


class TestCheckpointRestart:
    def test_resume_bitexact(self, tmp_path):
        model = tiny_model()
        d = str(tmp_path / "ckpt")
        # run 6 steps with checkpoint every 3
        tr1 = make_trainer(model, steps=6, ckpt_dir=d, ckpt_every=3)
        tr1.run()
        # fresh trainer restores step-6 state and continues to 9
        tr2 = make_trainer(model, steps=6, ckpt_dir=d, ckpt_every=3)
        state = tr2.init_state()
        state, start = tr2.maybe_restore(state)
        assert start == 6
        out2 = tr2.run(state=state, start_step=start, steps=3)
        # compare to an uninterrupted 9-step run
        tr3 = make_trainer(model, steps=9)
        out3 = tr3.run()
        p_resumed = jax.tree_util.tree_leaves(out2["state"]["params"])
        p_straight = jax.tree_util.tree_leaves(out3["state"]["params"])
        for a, b in zip(p_resumed, p_straight):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestElasticity:
    def test_shrink_on_fault_and_keep_training(self):
        model = tiny_model()
        faults = FaultInjector([FaultPlan(step=3, workers=(6, 7))])
        tr = make_trainer(model, steps=8, code="bgc", faults=faults)
        out = tr.run()
        ns = [h["n_workers"] for h in out["history"]]
        assert ns[0] == 8 and ns[-1] == 6
        assert all(np.isfinite(h["mean_ce"]) for h in out["history"])


class TestCompression:
    def test_int8_roundtrip_error_small(self):
        from repro.optim.compress import fake_quantize_int8
        x = jnp.asarray(np.random.default_rng(0).normal(size=(1000,)) * 0.01,
                        jnp.float32)
        y = fake_quantize_int8(x)
        rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
        assert rel < 0.01

    def test_training_with_compression_learns(self):
        model = tiny_model()
        tr = make_trainer(model, steps=12,
                          opt=OptConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=50, compress="int8"))
        out = tr.run()
        losses = [h["mean_ce"] for h in out["history"]]
        assert losses[-1] < losses[0]


class TestServing:
    def test_generate_batch(self):
        model = tiny_model()
        params = model.init(jax.random.PRNGKey(0))
        from repro.serving import ServingEngine
        eng = ServingEngine(model, params, batch_slots=2, cache_len=32)
        prompts = [np.array([1, 2, 3, 4], np.int32),
                   np.array([5, 6, 7, 8], np.int32)]
        outs = eng.generate_batch(prompts, max_new=4)
        assert len(outs) == 2 and all(len(o) == 4 for o in outs)
        assert all(0 <= t < model.cfg.padded_vocab for o in outs for t in o)
