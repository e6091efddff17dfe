"""repro.platform + TileConfig/autotune surface (docs/architecture.md §12).

In-process tests cover the pure pieces: TileConfig algebra and
validation, tile-resolution precedence (defaults < committed table <
explicit config < deprecated kwargs), hardware presets, backend-key
inference, and the bitwise contract of the committed autotune table.

The precedence rules that depend on a virgin jax — a pre-set env var
winning verbatim over configure(), the loud late-call RuntimeError, the
REPRO_* env entry point, and forced subprocess worlds — run in
subprocesses whose env is built by repro.platform.subprocess_env, the
same helper the differential suites use.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro import platform
from repro.core import codes
from repro.core.engine import DecodeEngine
from repro.kernels import ops
from repro.kernels.tiles import (DEFAULT_TILES, TileConfig, load_tile_table,
                                 resolve, shape_class)

REPO = Path(__file__).resolve().parent.parent

# vars the subprocess tests must own: start each child from an env with
# none of them so the test controls the whole precedence story
_JAX_VARS = ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64") + \
    platform._ENV_KEYS


def _clean_env(extra=None):
    """os.environ minus every var under test, plus ``extra``.

    Children that initialize jax WITHOUT selecting a platform first
    must put JAX_PLATFORMS=cpu in ``extra``: an unpinned jax probes
    for accelerators at backend init and can hang on bare containers.
    """
    env = dict(os.environ)
    for v in _JAX_VARS:
        env.pop(v, None)
    env.update(extra or {})
    return env


def _run_child(body: str, env: dict) -> dict:
    env = dict(env)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=560)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT:")]
    assert line, f"no RESULT in stdout:\n{out.stdout[-2000:]}"
    return json.loads(line[-1][len("RESULT:"):])


# ==========================================================================
# precedence rules (subprocess: each needs a virgin jax)
# ==========================================================================


@pytest.mark.slow
def test_preset_env_wins_verbatim_over_configure():
    """Rule 1: an exported XLA_FLAGS beats host_devices() outright."""
    env = _clean_env(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
         "JAX_PLATFORMS": "cpu"})
    res = _run_child("""
        import json
        from repro.platform import host_devices
        report = host_devices(8)
        import jax
        print("RESULT:" + json.dumps(
            {"report": report, "n": jax.device_count()}))
    """, env)
    assert res["report"]["XLA_FLAGS"] == "respected"
    assert res["n"] == 4            # pre-set env won verbatim, not our 8


@pytest.mark.slow
def test_configure_after_jax_init_raises():
    """Rule 2: a late configure() with work to do fails loudly instead
    of silently not taking effect (the old setdefault failure mode)."""
    res = _run_child("""
        import json, jax
        jax.devices()                        # lock the backend
        from repro.platform import configure
        try:
            configure(host_devices=8)
        except RuntimeError as e:
            print("RESULT:" + json.dumps({"raised": True, "msg": str(e)}))
        else:
            print("RESULT:" + json.dumps({"raised": False, "msg": ""}))
    """, _clean_env({"JAX_PLATFORMS": "cpu"}))
    assert res["raised"]
    assert "already initialized" in res["msg"]


@pytest.mark.slow
def test_late_x64_routes_through_jax_config():
    """Rule 3: x64 is runtime-togglable, so a late x64= goes through
    jax.config.update instead of raising."""
    res = _run_child("""
        import json, jax
        import jax.numpy as jnp
        jax.devices()
        from repro.platform import configure
        report = configure(x64=True)
        dt = str(jnp.zeros(1, jnp.float64).dtype)
        print("RESULT:" + json.dumps({"report": report, "dtype": dt}))
    """, _clean_env({"JAX_PLATFORMS": "cpu"}))
    assert res["report"]["JAX_ENABLE_X64"] == "set"
    assert res["dtype"] == "float64"


@pytest.mark.slow
def test_subprocess_env_round_trip():
    """subprocess_env renders the world the child actually gets."""
    env = platform.subprocess_env(_clean_env(), platform="cpu",
                                  host_devices=8, x64=True, override=True)
    res = _run_child("""
        import json, jax
        import jax.numpy as jnp
        from repro.platform import backend_info
        info = backend_info()
        print("RESULT:" + json.dumps({
            "n": jax.device_count(), "platform": info.platform,
            "key": info.key, "dtype": str(jnp.zeros(3).dtype),
            "peak": info.hardware.peak_flops}))
    """, env)
    assert res["n"] == 8
    assert res["platform"] == "cpu" and res["key"] == "cpu"
    assert res["dtype"] == "float64"        # x64 made it through
    assert res["peak"] == platform.HARDWARE["cpu"].peak_flops


@pytest.mark.slow
def test_configure_from_env_applies_repro_vars():
    """The CI lanes' entry point: REPRO_* -> a real device world."""
    env = _clean_env({"REPRO_PLATFORM": "cpu", "REPRO_HOST_DEVICES": "8"})
    res = _run_child("""
        import json
        from repro.platform import configure_from_env
        report = configure_from_env()
        import jax
        print("RESULT:" + json.dumps(
            {"report": report, "n": jax.device_count(),
             "backend": jax.default_backend()}))
    """, env)
    assert res["n"] == 8
    assert res["backend"] == "cpu"
    assert res["report"]["JAX_PLATFORMS"] == "set"
    assert res["report"]["XLA_FLAGS"] == "set"


# ==========================================================================
# pure pieces (in-process)
# ==========================================================================


def test_desired_env_composition():
    want = platform._desired_env("tpu", 4, None, None)
    # host_devices strips the tpu preset's own count flag, appends ours
    flags = want["XLA_FLAGS"]
    assert flags.count(platform._HOST_COUNT_FLAG) == 1
    assert f"{platform._HOST_COUNT_FLAG}=4" in flags
    assert "--xla_step_marker_location=1" in flags
    assert want["JAX_PLATFORMS"] == "tpu"
    with pytest.raises(ValueError):
        platform._desired_env("abacus", None, None, None)
    with pytest.raises(ValueError):
        platform._desired_env(None, 0, None, None)


def test_configure_from_env_is_noop_without_vars(monkeypatch):
    for v in _JAX_VARS:
        monkeypatch.delenv(v, raising=False)
    assert platform.configure_from_env() is None


def test_backend_key_env_inference(monkeypatch):
    # label-only path: jax uninitialized, key comes from the env
    monkeypatch.setattr(platform, "jax_is_initialized", lambda: False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("REPRO_PLATFORM", raising=False)
    assert platform.backend_key() == "cpu"
    monkeypatch.setenv("REPRO_PLATFORM", "tpu")
    assert platform.backend_key() == "tpu-v5e"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # JAX_PLATFORMS wins
    assert platform.backend_key() == "cpu"


def test_resolve_hardware_and_device_kind_mapping():
    assert platform.resolve_hardware("tpu") is platform.HARDWARE["tpu-v5e"]
    assert platform.resolve_hardware("gpu-h100").peak_flops == 989e12
    spec = platform.HARDWARE["cpu"]
    assert platform.resolve_hardware(spec) is spec
    with pytest.raises(KeyError):
        platform.resolve_hardware("abacus")
    assert platform._key_for("tpu", "TPU v5 lite") == "tpu-v5e"
    assert platform._key_for("gpu", "NVIDIA A100-SXM4-80GB") == "gpu-a100"
    assert platform._key_for("cpu", "whatever") == "cpu"


def test_resolve_hardware_none_needs_a_live_backend(monkeypatch):
    # no live backend: naming "the live device" is an error, not a guess
    monkeypatch.setattr(platform, "jax_is_initialized", lambda: False)
    with pytest.raises(RuntimeError, match="not initialized"):
        platform.resolve_hardware(None)
    # live backend: the live device's own preset
    monkeypatch.setattr(platform, "jax_is_initialized", lambda: True)
    assert platform.resolve_hardware(None) is platform.backend_info().hardware


@pytest.mark.parametrize("kind, key", [("TPU v5 lite", "tpu-v5e"),
                                       ("TPU v4", "tpu-v4"),
                                       ("TPU v9 ultra", None)])
def test_backend_info_unknown_tpu_kind_raises(monkeypatch, kind, key):
    import types

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda: [types.SimpleNamespace(device_kind=kind)])
    if key is None:
        with pytest.raises(KeyError, match="no hardware preset"):
            platform.backend_info()
    else:
        info = platform.backend_info()
        assert info.key == key and info.hardware is platform.HARDWARE[key]


def test_compile_cache_dir_respects_env(monkeypatch):
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert platform.enable_compile_cache() == "/elsewhere"
    assert updates == []          # the env var is JAX's own: nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = str(REPO / ".jax_cache")
    assert platform.enable_compile_cache() == path
    assert updates == [("jax_compilation_cache_dir", path)]


def test_roofline_reads_hardware_presets():
    from repro.launch import roofline

    t_cpu = roofline.roofline_terms(1e9, 1e6, 0.0, hardware="cpu",
                                    check_backend=False)
    t_tpu = roofline.roofline_terms(1e9, 1e6, 0.0, hardware="tpu-v5e",
                                    check_backend=False)
    assert t_cpu["hardware"] == "cpu" and t_tpu["hardware"] == "tpu-v5e"
    assert t_cpu["compute_s"] > t_tpu["compute_s"]   # cpu peak << tpu peak
    assert t_cpu["dominant"] in ("compute", "memory", "collective")


# ==========================================================================
# TileConfig + resolution precedence
# ==========================================================================


def test_tileconfig_validation_and_algebra():
    for bad in (dict(bb=0), dict(bk=-4), dict(bn=True), dict(bp=2.5)):
        with pytest.raises(ValueError):
            TileConfig(**bad)
    a = TileConfig(bb=64, bk=128)
    b = TileConfig(bk=256, bp=512)
    m = a.merged(b)                         # other's non-None fields win
    assert m == TileConfig(bb=64, bk=256, bp=512)
    assert a.merged(None) is a
    assert m.kwargs("coded_accumulate_batched") == {"bb": 64, "bk": 256,
                                                    "bp": 512}
    assert m.kwargs("batched_masked_gram") == {"bb": 64}  # bk not an axis
    assert m.as_dict() == {"bb": 64, "bk": 256, "bp": 512}


def test_shape_class_buckets():
    assert shape_class(None) == "scalar"
    assert shape_class(1) == "b1"
    assert shape_class(3) == "b1"
    assert shape_class(300) == "b128"
    assert shape_class(1000) == "b512"
    assert shape_class(1024) == "b1024"
    assert shape_class(10**6) == "b4096"


def test_resolve_defaults_match_historical_values():
    # no table for the backend, no explicit config -> exactly the
    # pre-redesign hardcoded tile sizes
    for kernel, cfg in DEFAULT_TILES.items():
        assert resolve(kernel, None, backend="no-such-backend",
                       B=None) == cfg.kwargs(kernel)
    with pytest.raises(KeyError):
        resolve("no_such_kernel", None, backend="cpu")


def test_resolve_precedence_with_table(tmp_path):
    p = tmp_path / "tiles.json"
    p.write_text(json.dumps({"cpu": {"batched_onestep_decode": {
        "b128": {"bb": 300}, "b32": {"bb": 48}}}}))
    # committed table beats defaults at its shape class
    kw = resolve("batched_onestep_decode", None, backend="cpu", B=300,
                 table_path=p)
    assert kw == {"bb": 300, "bk": 256, "bn": 256}
    # explicit TileConfig beats the table
    kw = resolve("batched_onestep_decode", TileConfig(bb=16), backend="cpu",
                 B=300, table_path=p)
    assert kw["bb"] == 16
    # nearest-smaller-bucket fallback: b512 absent -> the b128 pin serves
    assert resolve("batched_onestep_decode", None, backend="cpu", B=600,
                   table_path=p)["bb"] == 300
    # a backend with no table rides the defaults untouched
    assert resolve("batched_onestep_decode", None, backend="tpu-v5e",
                   B=300, table_path=p) == \
        DEFAULT_TILES["batched_onestep_decode"].kwargs(
            "batched_onestep_decode")


def test_legacy_tile_kwargs_warn_and_match():
    rng = np.random.default_rng(1)
    G = (rng.random((32, 32)) < 0.2).astype(np.float32)
    masks = (rng.random((16, 32)) < 0.9).astype(np.float32)
    rhos = np.ones(16, np.float32)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy = ops.batched_onestep_decode(G, masks, rhos,
                                            impl="pallas_interpret", bb=4)
    new = ops.batched_onestep_decode(G, masks, rhos,
                                     impl="pallas_interpret",
                                     tiles=TileConfig(bb=4))
    assert np.array_equal(np.asarray(legacy), np.asarray(new))
    with pytest.raises(TypeError, match="unexpected keyword"):
        ops.batched_onestep_decode(G, masks, rhos,
                                   impl="pallas_interpret", bz=4)


def test_engine_tiles_parameter_matches_numpy():
    code = codes.frc(k=32, n=32, s=4)
    rng = np.random.default_rng(2)
    masks = rng.random((24, 32)) < 0.85
    ref = DecodeEngine(code, backend="numpy").decode_batch(masks)
    tiled = DecodeEngine(code, backend="pallas_interpret",
                         tiles=TileConfig(bb=8)).decode_batch(masks)
    np.testing.assert_allclose(np.asarray(tiled.weights),
                               np.asarray(ref.weights),
                               atol=1e-5, rtol=1e-5)


# ==========================================================================
# the committed autotune table's bitwise contract
# ==========================================================================


def test_committed_table_bitwise_matches_defaults():
    """Every committed cpu tile entry must produce bitwise-identical
    outputs to the historical defaults (autotune only touches parallel
    grid axes; this is the acceptance check in test form)."""
    table = load_tile_table().get("cpu", {})
    assert table, "committed tile table is missing its cpu section"
    rng = np.random.default_rng(0)
    k, B, L, P = 64, 300, 32, 256
    G = (rng.random((k, k)) < 0.15).astype(np.float32)
    masks = (rng.random((B, k)) < 0.9).astype(np.float32)
    rhos = (rng.random(B) + 0.5).astype(np.float32)
    msgs = rng.standard_normal((L, P)).astype(np.float32)
    fmasks = (rng.random((B, L)) < 0.9).astype(np.float32)
    scales = (rng.random(B) + 0.5).astype(np.float32)
    grads = rng.standard_normal((k, P)).astype(np.float32)
    wts = rng.standard_normal((B, k)).astype(np.float32)
    gram = (G @ G.T).astype(np.float32)
    calls = {
        "batched_onestep_decode": lambda t: ops.batched_onestep_decode(
            G, masks, rhos, impl="pallas_interpret", tiles=t),
        "fused_decode_apply": lambda t: ops.fused_decode_apply(
            msgs, fmasks, scales, impl="pallas_interpret", tiles=t),
        "coded_accumulate_batched": lambda t: ops.coded_accumulate_batched(
            grads, wts, impl="pallas_interpret", tiles=t),
        "batched_masked_gram": lambda t: ops.batched_masked_gram(
            gram, masks, impl="pallas_interpret", tiles=t),
    }
    checked = 0
    for kernel in sorted(table):
        fn = calls.get(kernel)
        if fn is None:
            continue
        tuned = np.asarray(fn(None))        # defaults + committed table
        # a fully-specified explicit config bypasses the table outright
        default = np.asarray(fn(DEFAULT_TILES[kernel]))
        assert np.array_equal(tuned, default), kernel
        checked += 1
    assert checked >= 2


@pytest.mark.slow
def test_autotune_smoke_writes_loadable_table(tmp_path):
    from repro.launch import autotune

    p = tmp_path / "tiles.json"
    out = autotune.run(kernels=["batched_onestep_decode"], k=32,
                       batches=(32,), top=2, reps=1, table_path=p)
    assert out["backend"] == "cpu"
    assert out["records"] and all(
        r["rejected_bitwise"] == [] or r["best"] for r in out["records"])
    table = json.loads(p.read_text())
    assert set(table) <= {"cpu"}
    # whatever it pinned (possibly nothing) must load and resolve
    kw = resolve("batched_onestep_decode", None, backend="cpu", B=32,
                 table_path=p)
    assert set(kw) == {"bb", "bk", "bn"}
