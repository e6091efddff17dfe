"""Dropless expert dispatch over a held share of the experts, the per-row
aux loss, and the per-layer window and RoPE of a block pattern: a row's
gradient is its own (the coding identity and the single-device fold
need that), the shares of an expert layer add up to the whole layer,
and YaRN follows its published formula."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ArchConfig, MoEConfig, build_model
from repro.models import lm as LM
from repro.models import moe as moe_lib
from repro.models.config import YarnConfig
from repro.models.layers import yarn_inv_freq
from repro.models.spec import init_params
from repro.optim import OptConfig
from repro.training import CodedTrainConfig, CodedTrainer

YARN = YarnConfig(factor=16.0, original_max_position_embeddings=64)


def moe_cfg(dispatch="dropless", held=None, cf=1.25, **kw):
    """Four layers, one pattern period: three windowed layers (window 4)
    and a full YaRN one; 8 experts, top 2."""
    base = dict(
        name="t", family="moe", n_layers=4, d_model=32, n_heads=4, n_kv=2,
        d_head=8, d_ff=32, vocab=97, vocab_pad_to=32,
        block_pattern=("attn",) * 4, window_pattern=(4, 4, 4, 0),
        rope_pattern=("default",) * 3 + ("yarn",), yarn=YARN,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                      capacity_factor=cf, dispatch=dispatch, held=held),
        compute_dtype="float32", remat="none")
    base.update(kw)
    return ArchConfig(**base)


def skew(params, cfg, scale=4.0):
    """Weights under which expert 0 takes most tokens: a shared direction
    u in every embedding row, and every layer's router column 0 along
    it."""
    d = cfg.d_model
    u = np.zeros(d, np.float32)
    u[::2] = 1.0 / math.sqrt(d / 2)
    params = jax.tree_util.tree_map(lambda x: x, params)
    params["embed"] = params["embed"] + jnp.asarray(u)
    for blk in params["stack"].values():
        blk["moe"]["router"] = blk["moe"]["router"].at[..., 0].add(
            scale * jnp.asarray(u))
    return params


def batch_of(cfg, rows, seq, weight, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (rows, seq)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (rows, seq)),
                                  jnp.int32),
            "loss_weight": jnp.asarray(weight, jnp.float32)}


def flat(tree):
    return np.concatenate([np.asarray(x, np.float64).reshape(-1)
                           for x in jax.tree_util.tree_leaves(tree)])


# ----------------------------- the expert layer ----------------------------

def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    full = moe_cfg()
    params = init_params(moe_lib.moe_specs(full), jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 10, 32)),
                    jnp.float32)
    y, stats = moe_lib.moe_apply(params, x, full)
    # the uncut dropless layer is the dense oracle: nothing dropped
    y_dense, _ = moe_lib.moe_apply_dense(params, x, full)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=1e-5, atol=1e-6)
    total, routed = jnp.zeros_like(y), 0.0
    for first, count in ((0, 2), (2, 2), (4, 4)):
        cfg = moe_cfg(held=(first, count))
        share = dict(params, **{k: params[k][first:first + count]
                                for k in ("wi", "wg", "wo")})
        y_s, st = moe_lib.moe_apply(share, x, cfg)
        total = total + y_s
        routed += float(st.routed)
        # the router is whole on every share: the same per-row aux
        np.testing.assert_allclose(np.asarray(st.aux),
                                   np.asarray(stats.aux), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y),
                               rtol=1e-5, atol=1e-6)
    assert routed == float(stats.routed) == 3 * 10 * 2


def test_held_experts_are_the_stored_experts():
    cfg = moe_cfg(held=(3, 2))
    specs = moe_lib.moe_specs(cfg)
    assert specs["wi"].shape == (2, 32, 16)
    assert specs["router"].shape == (32, 8)
    with pytest.raises(ValueError):
        MoEConfig(num_experts=8, top_k=2, d_ff_expert=16, held=(0, 4))
    with pytest.raises(ValueError):
        MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                  dispatch="dropless", held=(6, 4))


def test_per_row_aux_is_the_switch_loss_of_the_row():
    cfg = moe_cfg()
    params = init_params(moe_lib.moe_specs(cfg), jax.random.PRNGKey(2))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 6, 32)),
                    jnp.float32)
    _, stats = moe_lib.moe_apply(params, x, cfg)
    for r in range(2):
        _, one = moe_lib.moe_apply_dense(params, x[r:r + 1], cfg)
        np.testing.assert_allclose(float(stats.aux[r]), float(one.aux[0]),
                                   rtol=1e-6)


@pytest.mark.parametrize("held", [(6, 2), (4, 4)])
def test_a_share_no_token_picks_adds_nothing(held):
    """Two or four held experts of top 2, none picked: every gate is
    zero, so the share's output and its experts' gradients are zero."""
    first, count = held
    cfg = moe_cfg(held=held)
    full = init_params(moe_lib.moe_specs(moe_cfg()), jax.random.PRNGKey(4))
    params = dict(full, **{k: full[k][first:first + count]
                           for k in ("wi", "wg", "wo")})
    # positive tokens, the held experts' router columns negative and the
    # others positive: no held expert is ever in the top 2
    params["router"] = jnp.where(jnp.arange(8) >= first, -1.0, 1.0) \
        * jnp.abs(params["router"])
    x = jnp.asarray(np.abs(np.random.default_rng(5).normal(size=(2, 6, 32)))
                    + 0.1, jnp.float32)

    def loss(p):
        y, st = moe_lib.moe_apply(p, x, cfg)
        return jnp.sum(y ** 2), (y, st)

    g, (y, st) = jax.grad(loss, has_aux=True)(params)
    assert float(st.routed) == 0.0
    assert not np.any(np.asarray(y))
    for k in ("wi", "wg", "wo"):
        assert not np.any(np.asarray(g[k]))


# ----------------------------- separability -------------------------------

def test_rows_are_separable_under_skewed_routing():
    """grad of sum_r w_r loss_r == sum_r w_r grad loss_r, one row at a
    time, with one expert taking most tokens; capacity dispatch, which
    drops by the whole batch's routing, does not give that."""
    weight = np.array([0.5, 0.25, 0.0, 0.125])

    def check(cfg):
        model = build_model(cfg)
        params = skew(model.init(jax.random.PRNGKey(0)), cfg)
        batch = batch_of(cfg, 4, 12, weight)
        grad = jax.jit(jax.grad(model.loss_fn, has_aux=True))
        g, met = grad(params, batch)
        rows = 0.0
        for r in range(4):
            one = {k: v[r:r + 1] for k, v in batch.items()}
            one["loss_weight"] = jnp.ones((1,), jnp.float32)
            rows = rows + weight[r] * flat(grad(params, one)[0])
        return met, flat(g), rows

    met, g, rows = check(moe_cfg())
    # most held assignments on one expert: 8 experts, top 2
    assert float(met["moe_max_load"]) > 2.5
    assert float(met["moe_routed"]) == 4 * 4 * 12 * 2
    np.testing.assert_allclose(g, rows, rtol=1e-4,
                               atol=1e-6 * np.abs(rows).max())
    # two held experts of top 2: the dense form of the held share
    met, g, rows = check(moe_cfg(held=(0, 2)))
    assert float(met["moe_max_load"]) > 1.5
    np.testing.assert_allclose(g, rows, rtol=1e-4,
                               atol=1e-6 * np.abs(rows).max())
    _, g_cap, rows_cap = check(moe_cfg(dispatch="global", cf=1.0))
    assert not np.allclose(g_cap, rows_cap, rtol=1e-3,
                           atol=1e-4 * np.abs(rows_cap).max())


def test_coded_trainer_folds_an_moe_model_exactly():
    """The folded batch of a dropless MoE gives the physical batch's
    gradient, under skewed routing."""
    cfg = moe_cfg()
    tr = CodedTrainer(build_model(cfg), CodedTrainConfig(
        code="frc", n_workers=8, s=2, decoder="onestep", rows_per_slot=1,
        seq_len=12, seed=0, exact_decode_renorm=False,
        opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=50)))
    model = tr.model
    params = skew(model.init(jax.random.PRNGKey(1)), cfg)
    mask = np.ones(8, dtype=bool)
    mask[[2, 6]] = False
    w = tr.decode_weights_for(mask)

    def grad(batch_np):
        b = {k: jnp.asarray(v) for k, v in batch_np.items()}
        return flat(jax.jit(jax.grad(lambda p, b: model.loss_fn(p, b)[0]))(
            params, b))

    phys = tr.pipeline.batch_for_step(0, w)
    fold = tr.pipeline.unique_batch_for_step(0, w)
    assert fold["tokens"].shape[0] < phys["tokens"].shape[0]
    g_p, g_f = grad(phys), grad(fold)
    scale = np.abs(g_p).max()
    np.testing.assert_allclose(g_f, g_p, rtol=1e-4, atol=1e-6 * scale)


@pytest.mark.parametrize("dispatch", ["global", "grouped"])
def test_coded_trainer_refuses_capacity_dispatch(dispatch):
    with pytest.raises(ValueError, match="dropless"):
        CodedTrainer(build_model(moe_cfg(dispatch=dispatch)),
                     CodedTrainConfig(code="frc", n_workers=8, s=2))


def test_history_reads_the_expert_counters():
    tr = CodedTrainer(build_model(moe_cfg(held=(0, 4))), CodedTrainConfig(
        code="frc", n_workers=8, s=2, decoder="onestep", rows_per_slot=1,
        seq_len=8, seed=0, steps=2, log_every=1))
    hist = tr.run()["history"]
    # 8 unique rows of 8 tokens, top 2 of 8 experts over 4 layers: about
    # half of the 512 assignments land on the 4 held experts
    for rec in hist:
        assert 0 < rec["moe_routed"] <= 8 * 8 * 2 * 4
        assert rec["moe_max_load"] >= 1.0
        assert rec["rows"] == 8
        assert rec["attn_blocks"] == 0   # 8 positions: no chunked path


def test_attn_live_blocks_of_the_mellum_period():
    """Three 1024-window layers and a full one at 4096 positions compute
    62 of their 4 x 32 (q, kv) block pairs; each further period as many;
    none where attention does not take the chunked path."""
    cfg = moe_cfg(window_pattern=(1024, 1024, 1024, 0))
    assert LM.attn_live_blocks(cfg, 4096) == 62
    assert LM.attn_live_blocks(dataclasses.replace(cfg, n_layers=8),
                               4096) == 124
    assert LM.attn_live_blocks(cfg, 512) == 0
    assert LM.attn_live_blocks(
        dataclasses.replace(cfg, attn_impl="xla_naive"), 4096) == 0


def test_history_counts_the_live_attention_blocks():
    """Past 512 positions the trainer's record counts the block pairs its
    chunked attention computed, from the batch's shapes."""
    cfg = ArchConfig(
        name="t", family="dense", n_layers=2, d_model=16, n_heads=2, n_kv=1,
        d_head=8, d_ff=16, vocab=97, vocab_pad_to=32,
        block_pattern=("attn",) * 2, window_pattern=(256, 0),
        compute_dtype="float32", remat="none")
    tr = CodedTrainer(build_model(cfg), CodedTrainConfig(
        code="frc", n_workers=4, s=2, decoder="onestep", rows_per_slot=1,
        seq_len=2048, seed=0, steps=1, log_every=1))
    rec = tr.run()["history"][-1]
    # q blocks of 512 over kv blocks of 1024: the 256-window layer 5 of 8,
    # the full layer 6 of 8
    assert rec["attn_blocks"] == LM.attn_live_blocks(cfg, 2048) == 11


# --------------------------- windows and RoPE ------------------------------

def test_yarn_frequencies_at_mellum_values():
    """d 128, base 5e5, factor 16 over 8192 positions, beta 32 and 1."""
    y = YarnConfig(factor=16.0, original_max_position_embeddings=8192,
                   beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782)
    inv, scale = yarn_inv_freq(128, 5e5, y)
    d, b = 128, 5e5

    def fcd(r):
        return d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(b))
    lo, hi = math.floor(fcd(32)), math.ceil(fcd(1))
    assert (lo, hi) == (18, 35)
    i = np.arange(64)
    base = b ** (-2 * i / d)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(inv, base / 16 * ramp + base * (1 - ramp),
                               rtol=1e-15)
    # high frequencies kept, low ones interpolated by the factor
    np.testing.assert_allclose(inv[:lo + 1], base[:lo + 1], rtol=1e-15)
    np.testing.assert_allclose(inv[hi:], base[hi:] / 16, rtol=1e-15)
    assert scale == 1.2772588722239782
    # the default attention factor: 0.1 ln(factor) + 1
    _, s2 = yarn_inv_freq(128, 5e5, dataclasses.replace(
        y, attention_factor=None))
    assert s2 == pytest.approx(1.2772588722239782, rel=1e-12)


def test_arch_config_from_json_lists_and_dicts():
    cfg = ArchConfig(
        name="j", family="moe", n_layers=4, d_model=32, n_heads=4, n_kv=2,
        d_ff=16, vocab=64, block_pattern=["attn"] * 4,
        window_pattern=[8, 8, 8, 0],
        rope_pattern=["default", "default", "default", "yarn"],
        yarn={"factor": 16.0, "original_max_position_embeddings": 64},
        moe={"num_experts": 8, "top_k": 2, "d_ff_expert": 16,
             "dispatch": "dropless", "held": [2, 4]})
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.held == (2, 4)
    assert isinstance(cfg.yarn, YarnConfig)
    assert cfg.block_pattern == ("attn",) * 4
    assert [cfg.window_at(i) for i in range(4)] == [8, 8, 8, 0]
    assert [cfg.yarn_at(i) for i in range(4)] == [None] * 3 + [cfg.yarn]
    hash(cfg)
    # 4 held experts of 8: 3 matrices each, plus the 8-wide router
    assert cfg.param_count() == build_model(cfg).param_count()
    with pytest.raises(ValueError):
        ArchConfig(name="j", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv=4, d_ff=16, vocab=64,
                   window_pattern=[8, 0])


def test_each_layer_keeps_its_own_window_and_rope():
    """Changing the window of the windowed layers, or the YaRN layer's
    settings, changes the loss; a window longer than the sequence is the
    same as none."""
    cfg = moe_cfg(family="dense", moe=None)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    batch = batch_of(cfg, 2, 12, [0.5, 0.5])

    def loss(c):
        return float(build_model(c).loss_fn(params, batch)[0])

    base = loss(cfg)
    assert loss(dataclasses.replace(cfg, window_pattern=(12, 12, 12, 0))) \
        == pytest.approx(loss(dataclasses.replace(
            cfg, window_pattern=())), rel=1e-6)
    assert abs(loss(dataclasses.replace(cfg, window_pattern=(0, 0, 0, 0)))
               - base) > 1e-4
    assert abs(loss(dataclasses.replace(cfg, rope_pattern=())) - base) \
        > 1e-4


def test_cached_decode_matches_the_forward_with_per_layer_windows():
    """Ring caches sized by each layer's window: prefill then decode
    gives the logits of one full forward."""
    cfg = moe_cfg(family="dense", moe=None)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    caches = LM.init_lm_cache(cfg, 1, 16, jnp.float32)
    rings = [caches["stack"][f"p{i}"]["k"].shape[2] for i in range(4)]
    assert rings == [4, 4, 4, 16]
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 97, (1, 14)),
                         jnp.int32)
    logits, _ = LM.lm_forward(params, cfg, {"tokens": tokens})
    last, caches = LM.lm_prefill(params, cfg, {"tokens": tokens[:, :8]}, 16)
    got = [last]
    for t in range(8, 14):
        step, caches = LM.lm_decode_step(params, cfg, tokens[:, t:t + 1],
                                         caches)
        got.append(step)
    want = logits[0, 7:14]
    np.testing.assert_allclose(np.stack([np.asarray(g[0]) for g in got]),
                               np.asarray(want), rtol=2e-4, atol=2e-4)
