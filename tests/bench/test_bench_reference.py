"""The benchmark's plain float32 reference against the program's model at
small widths on the CPU: the same weights give the same weighted loss
and the same gradient, for every mechanism of both configurations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import dense_lm

MECHANISMS = {
    # minicpm-2b's: RMSNorm, SwiGLU, MHA, RoPE, tied head
    "minicpm-2b": dict(n_kv=4),
    # starcoder2-7b's: LayerNorm, GeLU, GQA, QKV bias, untied head
    "starcoder2-7b": dict(n_kv=2),
}


def small(config: str) -> dict:
    m = harness.load_cell(
        "minicpm-2b.coded-frc8" if config == "minicpm-2b"
        else "starcoder2-7b.coded-bgc8-seq512")["config"]["model"]
    m = dict(m, n_layers=2, d_model=64, n_heads=4, d_head=16, d_ff=96,
             vocab=203, vocab_pad_to=64, loss_chunk=0,
             compute_dtype="float32", remat="none", **MECHANISMS[config])
    return m


@pytest.mark.parametrize("config", sorted(MECHANISMS))
def test_reference_matches_program_loss_and_grad(config):
    from repro.models import ArchConfig, build_model

    m = small(config)
    model = build_model(ArchConfig(**m))
    params = dense_lm.init_params(m, jax.random.PRNGKey(3))
    # the benchmark's weights fit the program's tree, leaf for leaf
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(want)
    assert [p.shape for p in jax.tree_util.tree_leaves(params)] == \
        [p.shape for p in jax.tree_util.tree_leaves(want)]
    # non-zero biases and norm offsets, so that each one's path is used
    bumped = ("['scale']", "['bias']", "['bq']", "['bk']", "['bv']",
              "['bi']", "['bo']")
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 0.05
        if jax.tree_util.keystr(path).endswith(bumped) else p, params)
    rng = np.random.default_rng(0)
    R, S = 3, 12
    tokens = rng.integers(0, m["vocab"], (R, S)).astype(np.int32)
    labels = rng.integers(0, m["vocab"], (R, S)).astype(np.int32)
    weight = np.array([0.5, 0.0, 0.25], np.float32)

    def prog(p):
        loss, _ = model.loss_fn(p, {"tokens": jnp.asarray(tokens),
                                    "labels": jnp.asarray(labels),
                                    "loss_weight": jnp.asarray(weight)})
        return loss

    def ref(p):
        with jax.default_matmul_precision("highest"):
            ce = dense_lm.row_ce(p, m, jnp.asarray(tokens),
                                 jnp.asarray(labels))
            return jnp.sum(ce * jnp.asarray(weight))

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(params)
    lr, gr = jax.value_and_grad(ref)(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5 * float(jnp.abs(b).max() + 1e-9))


def test_reference_adamw_matches_program_optimizer():
    """Three reference steps against the program's AdamW and schedule on
    the same gradients: the clipped first gradient and the change."""
    from repro.optim import OptConfig, adamw_update, init_opt_state
    from repro.optim.schedules import make_schedule

    spec = harness.load_cell("minicpm-2b.coded-frc8")
    opt = spec["traffic"]["opt"]
    oc = OptConfig(**{k: v for k, v in opt.items() if k != "decay"})
    m = small("starcoder2-7b")
    p0 = dense_lm.init_params(m, jax.random.PRNGKey(1))
    grads = [jax.tree_util.tree_map(
        lambda p, i=i: jnp.full_like(p, 0.3) * (1 + i) + 0.1 * p, p0)
        for i in range(3)]
    sched = make_schedule("wsd", oc.lr, oc.total_steps, oc.warmup_steps,
                          oc.min_ratio, oc.decay_frac)
    p, st = p0, init_opt_state(p0)
    for g in grads:
        p, st, _ = adamw_update(p, g, st, oc, sched(st["step"]))
    q, mu, nu = p0, None, None
    for t, g in enumerate(grads):
        gn = dense_lm.leaf_norms(g)
        scale = min(1.0, opt["clip_norm"] / np.sqrt(np.sum(gn ** 2)))
        q, mu, nu = dense_lm._adamw(q, g, mu, nu, scale,
                                    dense_lm.lr_at(opt, t), t + 1, opt)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(q)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-9)


def test_fp8_control_rounds_operands_not_the_reference():
    a = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    q = dense_lm._q(a, jnp.float8_e4m3fn)
    assert float(jnp.max(jnp.abs(q - a))) > 1e-3
    assert float(jnp.max(jnp.abs(q - a) / jnp.maximum(jnp.abs(a), 0.1))) \
        < 0.07
    es = dense_lm._einsum_fn("fp8")
    f = lambda x: jnp.sum(es("ij,jk->ik", x, a))  # noqa: E731
    g = jax.grad(f)(a)
    assert g.shape == a.shape and bool(jnp.all(jnp.isfinite(g)))
