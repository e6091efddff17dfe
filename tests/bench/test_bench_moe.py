"""The Mellum2-12B-A2.5B configuration and its cell: the parameter count
of the chip's share worked out by hand, the cell's files, and the plain
float32 MoE reference against the program at small widths on the CPU
(and against the dense reference where the two overlap)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import dense_lm, moe_lm

CONFIG = "mellum2-12b-a2.5b"
CELL = "mellum2-12b-a2.5b.coded-frc8-seq4096"
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def model_config() -> dict:
    return harness.load_cell(CELL)["config"]["model"]


def test_parameter_count_of_the_share_by_hand():
    """Per layer: q and o 2304 x 4096 each, k and v 2304 x 512 each; 8
    held SwiGLU experts of three 2304 x 896 matrices; a 2304 x 64
    router; two norms.  An untied 12288-row embedding and head, and the
    final norm: 70.93 M a layer, 340.35 M in all."""
    from repro.models import ArchConfig, build_model

    d, f, V = 2304, 896, 12288
    attn = 2 * d * 4096 + 2 * d * 512
    layer = attn + 8 * 3 * d * f + d * 64 + 2 * d
    total = 4 * layer + 2 * V * d + d
    assert layer == pytest.approx(70.93e6, abs=0.005e6)
    assert total == pytest.approx(340.35e6, abs=0.005e6)
    cfg = ArchConfig(**model_config())
    assert cfg.param_count() == total
    assert build_model(cfg).param_count() == total
    shapes = jax.tree_util.tree_leaves(moe_lm.param_shapes(model_config()),
                                       is_leaf=moe_lm._is_shape)
    assert sum(int(np.prod(s)) for s in shapes) == total


def test_the_configuration_states_its_cut():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert cfg["source"] == entry["source"]
    for key in ("published", "reduced", "assumed", "not_run", "deployment"):
        assert cfg[key]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == \
        set(cfg["published"])
    for key in entry["reduced"]:
        assert cfg[key] != cfg["published"][key]
    m = cfg["model"]
    # widths as published; the share: 8 of 64 experts, an eighth of the
    # vocabulary, one period of four layers
    assert (m["d_model"], m["n_heads"], m["n_kv"], m["d_head"]) == \
        (cfg["hidden_size"], cfg["num_attention_heads"],
         cfg["num_key_value_heads"], cfg["head_dim"])
    assert m["moe"]["num_experts"] == cfg["published"]["num_experts"]
    assert m["moe"]["held"][1] == cfg["num_experts"]
    assert m["moe"]["top_k"] == cfg["num_experts_per_tok"]
    assert m["moe"]["d_ff_expert"] == cfg["moe_intermediate_size"]
    assert m["vocab"] == cfg["vocab_size"] == \
        cfg["published"]["vocab_size"] // 8
    kinds = cfg["layer_types"][:m["n_layers"]]
    assert m["window_pattern"] == [cfg["sliding_window"]
                                   if k == "sliding_attention" else 0
                                   for k in kinds]
    assert m["rope_pattern"] == ["yarn" if k == "full_attention"
                                 else "default" for k in kinds]
    full = cfg["rope_parameters"]["full_attention"]
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"):
        assert m["yarn"][key] == full[key]
    assert m["rope_theta"] == full["rope_theta"] == \
        cfg["rope_parameters"]["sliding_attention"]["rope_theta"]


def test_the_cell_trains_8_rows_of_4096_a_step():
    spec = harness.load_cell(CELL)
    tr = spec["traffic"]["trainer"]
    assert (tr["seq_len"], tr["rows_per_slot"], tr["dist_mode"]) == \
        (4096, 1, "fused")
    code = spec["traffic"]["code"]
    assert (code["family"], code["n"], code["s"]) == ("frc", 8, 2)
    G = np.asarray(code["G"])
    assert (G.sum(axis=1) == 2).all()           # each task on two workers
    assert set(spec["limits"]) >= {"loss", "grad", "update"}
    base = harness.load_cell("minicpm-2b.uncoded-baseline")["traffic"]
    Gb = np.asarray(base["code"]["G"])
    assert (Gb.sum(axis=0) == 1).all() and (Gb.sum(axis=1) == 1).all()
    assert base["sync_policy"]["deadline"] > 1e6


def small(**over) -> dict:
    m = dict(model_config(), n_layers=4, d_model=64, n_heads=4, n_kv=2,
             d_head=16, vocab=203, vocab_pad_to=64, loss_chunk=0,
             compute_dtype="float32", remat="none",
             window_pattern=[4, 4, 4, 0])
    m["moe"] = dict(m["moe"], num_experts=16, top_k=4, d_ff_expert=24,
                    held=[0, 8])
    m.update(over)
    return m


def grads(m, params, tokens, labels, weight):
    from repro.models import ArchConfig, build_model

    model = build_model(ArchConfig(**m))

    def prog(p):
        loss, met = model.loss_fn(p, {"tokens": jnp.asarray(tokens),
                                      "labels": jnp.asarray(labels),
                                      "loss_weight": jnp.asarray(weight)})
        return loss, (met["loss"], met["mean_ce"])

    def ref(p):
        with jax.default_matmul_precision("highest"):
            ce, aux = moe_lm.row_losses(p, m, jnp.asarray(tokens),
                                        jnp.asarray(labels))
            w = jnp.asarray(weight)
            loss = jnp.sum((ce + 0.01 * aux) * w)
            return loss, (jnp.sum(ce * w), jnp.mean(ce))

    with jax.default_matmul_precision("highest"):
        out_p = jax.jit(jax.value_and_grad(prog, has_aux=True))(params)
    return out_p, jax.jit(jax.value_and_grad(ref, has_aux=True))(params)


@pytest.mark.parametrize("n_layers,top_k", [(4, 4), (6, 4), (4, 8)])
def test_reference_matches_program_loss_and_grad(n_layers, top_k):
    """One period (three layers with a window of 4 over 12 positions, a
    full YaRN layer) and a period and a half (a stacked group and two
    unstacked layers); 8 of 16 experts held, top 4 (more held than a
    token picks) or top 8 as in the cell."""
    from repro.models import ArchConfig, build_model

    m = small(n_layers=n_layers)
    m["moe"] = dict(m["moe"], top_k=top_k)
    params = moe_lm.init_params(m, jax.random.PRNGKey(3))
    want = jax.eval_shape(build_model(ArchConfig(**m)).init,
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(want)
    assert [p.shape for p in jax.tree_util.tree_leaves(params)] == \
        [p.shape for p in jax.tree_util.tree_leaves(want)]
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 0.05
        if jax.tree_util.keystr(path).endswith("['scale']") else p, params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, m["vocab"], (3, 12)).astype(np.int32)
    labels = rng.integers(0, m["vocab"], (3, 12)).astype(np.int32)
    weight = np.array([0.5, 0.0, 0.25], np.float32)
    ((lp, (logged, cp)), gp), ((lr, (wce, cr)), gr) = grads(
        m, params, tokens, labels, weight)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    # the logged loss is the weighted CE; the objective adds the aux
    np.testing.assert_allclose(float(logged), float(wce), rtol=1e-5)
    assert float(lp) > float(logged)
    np.testing.assert_allclose(float(cp), float(cr), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5 * float(jnp.abs(b).max() + 1e-9))


def test_reference_is_the_dense_reference_where_they_overlap():
    """One expert, top 1, no window, plain RoPE: every gate is 1, the
    expert is a SwiGLU MLP and each row's aux is 1, so the row CEs are
    dense_lm's with the expert's weights as the MLP."""
    m = small(window_pattern=[0] * 4, rope_pattern=[], yarn=None)
    m["moe"] = dict(m["moe"], num_experts=1, top_k=1, held=[0, 1])
    params = moe_lm.init_params(m, jax.random.PRNGKey(5))
    layers = [moe_lm.layer_params(params, m, i) for i in range(4)]

    def stacked(get):
        return jnp.stack([get(p) for p in layers])

    dense = {"embed": params["embed"], "final_norm": params["final_norm"],
             "head": params["head"], "rem": {}, "stack": {"p0": {
                 "ln1": {"scale": stacked(lambda p: p["ln1"]["scale"])},
                 "ln2": {"scale": stacked(lambda p: p["ln2"]["scale"])},
                 "attn": {k: stacked(lambda p, k=k: p["attn"][k])
                          for k in ("wq", "wk", "wv", "wo")},
                 "mlp": {k: stacked(lambda p, k=k: p["moe"][k][0])
                         for k in ("wg", "wi", "wo")}}}}
    dm = dict(m, qkv_bias=False, d_ff=m["moe"]["d_ff_expert"])
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, m["vocab"], (2, 10)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, m["vocab"], (2, 10)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ce, aux = moe_lm.row_losses(params, m, tokens, labels)
        want = dense_lm.row_ce(dense, dm, tokens, labels)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(aux), 4.0, rtol=1e-6)
