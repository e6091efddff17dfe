"""repro.platform — the one import-time-safe backend configuration API.

Every knob that must be decided *before* jax initializes its backends
(platform selection, host CPU device-count worlds, x64, XLA flag
presets) lives here, with one documented precedence rule instead of the
ad-hoc ``XLA_FLAGS=`` strings the tests/CI used to carry:

    1. A PRE-SET environment variable wins VERBATIM.  ``configure()``
       never overwrites ``XLA_FLAGS`` / ``JAX_PLATFORMS`` /
       ``JAX_ENABLE_X64`` that the caller (or CI lane) already exported
       — so an outer world always beats an inner default, exactly the
       setdefault contract launch/dryrun.py pioneered.
    2. ``configure()`` must run before jax initializes its backends.
       If it still has assignments to make after the env was consulted
       and jax is already initialized, it raises RuntimeError loudly
       (the old setdefault was silently ineffective in that case).
    3. x64 is the one exception: jax supports toggling it at runtime,
       so a late ``x64=`` goes through ``jax.config.update`` instead of
       raising (a pre-set ``JAX_ENABLE_X64`` still wins).

Entry points:

    configure(platform=, host_devices=, x64=, preset=)  the full API
    host_devices(n)              CPU host-device world (tests, dryrun)
    configure_from_env()         REPRO_PLATFORM / REPRO_HOST_DEVICES /
                                 REPRO_X64 env — how CI lanes export
                                 their world through this module
    subprocess_env(...)          same decisions rendered into an env
                                 dict for a child process (the
                                 differential-test subprocess helper)
    backend_info()               live (platform, devices, hardware
                                 spec); backend_key() is the stable
                                 string the bench baselines key on
    enable_compile_cache()       JAX's persistent compile cache for the
                                 mains (never called on import)
    HARDWARE / resolve_hardware  per-backend peak FLOPs / HBM / link
                                 bandwidth presets (launch/roofline.py
                                 reads these instead of hardcoding
                                 TPU-v5e constants)

This module imports no jax at module scope, so it is safe to import
first in any process.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path
from typing import Dict, Optional, Union

__all__ = [
    "HardwareSpec", "HARDWARE", "PRESETS", "BackendInfo",
    "configure", "host_devices", "configure_from_env", "subprocess_env",
    "backend_info", "backend_key", "runtime_platform", "resolve_hardware",
    "jax_is_initialized", "enable_compile_cache",
]


# --------------------------------------------------------------------------
# hardware presets (feed launch/roofline.py and launch/autotune.py)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-device roofline constants for one backend.

    Peak numbers are the marketing matmul peaks (bf16 where the backend
    has one); the CPU entry is an order-of-magnitude estimate for a
    modern multicore host (AVX fp32 + dual-channel DDR) — good enough
    to rank tile candidates and to label CPU bench baselines, not a
    calibrated model.
    """

    name: str            # stable key ("tpu-v5e", "gpu-a100", "cpu")
    platform: str        # jax backend name: "tpu" | "gpu" | "cpu"
    peak_flops: float    # FLOP/s per device
    hbm_bw: float        # main-memory bandwidth, B/s per device
    link_bw: float       # interconnect bandwidth, B/s per link
    vmem_bytes: int      # fast scratch budget per core (tile feasibility)


HARDWARE: Dict[str, HardwareSpec] = {
    # TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI link,
    # ~16 MB VMEM/core (the constants launch/roofline.py used to inline)
    "tpu-v5e": HardwareSpec("tpu-v5e", "tpu", 197e12, 819e9, 50e9,
                            16 * 2**20),
    "tpu-v4": HardwareSpec("tpu-v4", "tpu", 275e12, 1228e9, 50e9,
                           16 * 2**20),
    "gpu-a100": HardwareSpec("gpu-a100", "gpu", 312e12, 2039e9, 600e9,
                             40 * 2**20),   # SMEM+L2 working-set budget
    "gpu-h100": HardwareSpec("gpu-h100", "gpu", 989e12, 3350e9, 900e9,
                             50 * 2**20),
    # host CPU estimate: ~0.5 TFLOP/s fp32 across cores, ~50 GB/s DDR,
    # "link" = memory bus shared between host devices, LLC as scratch
    "cpu": HardwareSpec("cpu", "cpu", 5e11, 5e10, 5e10, 32 * 2**20),
}

# the spec assumed when only the platform is known
_PLATFORM_DEFAULT_HW = {"tpu": "tpu-v5e", "gpu": "gpu-a100", "cpu": "cpu"}

# device_kind substrings -> HARDWARE keys (first match wins)
_DEVICE_KIND_MAP = (
    ("v5 lite", "tpu-v5e"), ("v5e", "tpu-v5e"), ("v4", "tpu-v4"),
    ("h100", "gpu-h100"), ("a100", "gpu-a100"),
)


def resolve_hardware(hw: Union[None, str, HardwareSpec]) -> HardwareSpec:
    """HardwareSpec from a spec, a HARDWARE key, or a platform name.

    ``None`` means the live device, which jax must already have
    initialized; an unknown device raises (see :func:`backend_info`).
    """
    if isinstance(hw, HardwareSpec):
        return hw
    if hw is None:
        if not jax_is_initialized():
            raise RuntimeError(
                "resolve_hardware(None) names the live device, but jax has "
                "not initialized a backend; pass a HARDWARE key "
                f"{sorted(HARDWARE)} or a platform name")
        return backend_info().hardware
    if hw in HARDWARE:
        return HARDWARE[hw]
    if hw in _PLATFORM_DEFAULT_HW:
        return HARDWARE[_PLATFORM_DEFAULT_HW[hw]]
    raise KeyError(f"unknown hardware {hw!r}; have {sorted(HARDWARE)} "
                   f"or a platform in {sorted(_PLATFORM_DEFAULT_HW)}")


# --------------------------------------------------------------------------
# XLA flag presets per backend
# --------------------------------------------------------------------------

# Documented env presets.  Each maps env var -> value; applied with the
# pre-set-env-wins rule.  The "cpu" preset is empty on purpose — CPU
# worlds are defined by host_devices(n), which composes the
# --xla_force_host_platform_device_count flag itself.
PRESETS: Dict[str, Dict[str, str]] = {
    "cpu": {},
    # the gpu autotune / latency-hiding flag set (bayespec's
    # set_platform gpu branch, minus the long-removed flags)
    "gpu": {
        "XLA_FLAGS": ("--xla_gpu_triton_gemm_any=True "
                      "--xla_gpu_enable_latency_hiding_scheduler=true"),
    },
    # the TPU process env distilled from olmax's run.sh: one host
    # device (TPU-CPU is not used for ML), step markers at the outer
    # while loop for profiling, quiet TF logging
    "tpu": {
        "XLA_FLAGS": ("--xla_force_host_platform_device_count=1 "
                      "--xla_step_marker_location=1"),
        "TF_CPP_MIN_LOG_LEVEL": "4",
    },
}

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


# --------------------------------------------------------------------------
# jax state probes (no jax import unless already present)
# --------------------------------------------------------------------------


def jax_is_initialized() -> bool:
    """True once jax has created a backend (device count is locked)."""
    if "jax" not in sys.modules:
        return False
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    return bool(getattr(xb, "_backends", None))


def runtime_platform() -> Optional[str]:
    """The live jax backend name, or None when jax is uninitialized.

    Never initializes jax itself — callers that only want to *label*
    (roofline warnings, bench baselines) must not pay backend startup.
    """
    if not jax_is_initialized():
        return None
    import jax

    return jax.default_backend()


# --------------------------------------------------------------------------
# configure
# --------------------------------------------------------------------------


def _desired_env(platform: Optional[str], host_devices: Optional[int],
                 x64: Optional[bool], preset: Optional[str]) -> Dict[str, str]:
    """The env assignments configure()/subprocess_env() agree on."""
    if platform is not None and platform not in PRESETS:
        raise ValueError(f"platform {platform!r} not in {sorted(PRESETS)}")
    if preset is None:
        preset = platform
    if preset is not None and preset not in PRESETS:
        raise ValueError(f"preset {preset!r} not in {sorted(PRESETS)}")

    want: Dict[str, str] = dict(PRESETS[preset]) if preset else {}
    if platform is not None:
        want["JAX_PLATFORMS"] = platform
    if host_devices is not None:
        n = int(host_devices)
        if n <= 0:
            raise ValueError(f"host_devices must be positive, got {n}")
        flag = f"{_HOST_COUNT_FLAG}={n}"
        base = want.get("XLA_FLAGS", "")
        if _HOST_COUNT_FLAG in base:   # preset carried a count: ours wins
            base = " ".join(f for f in base.split()
                            if not f.startswith(_HOST_COUNT_FLAG))
        want["XLA_FLAGS"] = (base + " " + flag).strip()
    if x64 is not None:
        want["JAX_ENABLE_X64"] = "1" if x64 else "0"
    return want


def configure(platform: Optional[str] = None,
              host_devices: Optional[int] = None,
              x64: Optional[bool] = None,
              preset: Optional[str] = None) -> Dict[str, str]:
    """Configure the jax world for this process.  Call before jax inits.

    Returns a report dict mapping each env var this call considered to
    ``"set"`` (we exported it) or ``"respected"`` (a pre-set value won
    verbatim — precedence rule 1).  Raises RuntimeError when an
    assignment is still needed but jax already initialized (rule 2);
    ``x64`` alone falls through to ``jax.config.update`` (rule 3).
    """
    want = _desired_env(platform, host_devices, x64, preset)
    report: Dict[str, str] = {}
    late_x64 = None
    for var, val in want.items():
        if var in os.environ:
            report[var] = "respected"
            continue
        if var == "JAX_ENABLE_X64" and "jax" in sys.modules:
            # runtime-togglable: route through jax.config instead of an
            # env var jax has already read
            late_x64 = val == "1"
            report[var] = "set"
            continue
        if jax_is_initialized():
            raise RuntimeError(
                f"repro.platform.configure() would set {var}={val!r}, but "
                f"jax already initialized its "
                f"{runtime_platform()!r} backend — the setting cannot take "
                f"effect.  Call configure() before the first jax device "
                f"use (typically first thing in the process), or export "
                f"the environment variable before launching.")
        os.environ[var] = val
        report[var] = "set"
    if late_x64 is not None:
        import jax

        jax.config.update("jax_enable_x64", late_x64)
    return report


def host_devices(n: int, *, x64: Optional[bool] = None) -> Dict[str, str]:
    """An ``n``-device host CPU world (tests, dry-runs, differentials).

    Sugar for ``configure(host_devices=n, x64=x64)`` — same precedence
    rules: a pre-set ``XLA_FLAGS`` wins verbatim, calling after jax
    initialized (with work left to do) raises.
    """
    return configure(host_devices=n, x64=x64)


_ENV_KEYS = ("REPRO_PLATFORM", "REPRO_HOST_DEVICES", "REPRO_X64",
             "REPRO_PRESET")


def configure_from_env() -> Optional[Dict[str, str]]:
    """Apply REPRO_* env configuration (the CI lanes' entry point).

    Reads REPRO_PLATFORM / REPRO_HOST_DEVICES / REPRO_X64 /
    REPRO_PRESET and calls :func:`configure` when any is set (no-op
    otherwise, so unconfigured local runs are untouched).  Called from
    tests/conftest.py, which runs before any test imports jax.
    """
    if not any(k in os.environ for k in _ENV_KEYS):
        return None
    hd = os.environ.get("REPRO_HOST_DEVICES")
    x64 = os.environ.get("REPRO_X64")
    return configure(
        platform=os.environ.get("REPRO_PLATFORM"),
        host_devices=int(hd) if hd else None,
        x64=(x64 not in ("0", "false", "False")) if x64 is not None else None,
        preset=os.environ.get("REPRO_PRESET"),
    )


def subprocess_env(base: Optional[Dict[str, str]] = None, *,
                   platform: Optional[str] = None,
                   host_devices: Optional[int] = None,
                   x64: Optional[bool] = None,
                   preset: Optional[str] = None,
                   override: bool = False) -> Dict[str, str]:
    """Env dict for a child process with the requested jax world.

    The one place the differential tests get their forced-device
    subprocess env from.  ``override=False`` follows the standard
    precedence (vars already in ``base`` win); ``override=True``
    assigns unconditionally — for tests that *assert* an exact world
    (e.g. ``jax.device_count() == 8``) regardless of the caller's env.
    """
    env = dict(os.environ if base is None else base)
    for var, val in _desired_env(platform, host_devices, x64,
                                 preset).items():
        if override or var not in env:
            env[var] = val
    return env


# --------------------------------------------------------------------------
# backend reporting
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendInfo:
    """What the live jax world looks like, roofline constants included."""

    platform: str            # "cpu" | "gpu" | "tpu"
    device_count: int
    device_kind: str         # jax's device_kind string
    key: str                 # stable baseline key ("cpu", "tpu-v5e", ...)
    hardware: HardwareSpec   # peak FLOPs / HBM bw / link bw preset

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["hardware"] = dataclasses.asdict(self.hardware)
        return d


def _key_for(platform: str, device_kind: str) -> str:
    if platform == "cpu":
        return "cpu"
    kind = device_kind.lower()
    for sub, key in _DEVICE_KIND_MAP:
        if sub in kind:
            return key
    slug = "-".join(kind.split()) or platform
    return slug if slug.startswith(platform) else f"{platform}-{slug}"


def backend_info() -> BackendInfo:
    """Live world report.  Initializes jax (device query) if needed.

    Raises KeyError for a device with no HARDWARE preset: its peaks are
    unknown, and guessing another chip's would mislabel every number.
    """
    import jax

    platform = jax.default_backend()
    devices = jax.devices()
    kind = getattr(devices[0], "device_kind", platform)
    key = _key_for(platform, kind)
    hw = HARDWARE.get(key)
    if hw is None:
        raise KeyError(f"no hardware preset for {platform} device_kind "
                       f"{kind!r} (key {key!r}); add it to "
                       f"repro.platform.HARDWARE and _DEVICE_KIND_MAP")
    return BackendInfo(platform=platform, device_count=len(devices),
                       device_kind=kind, key=key, hardware=hw)


def backend_key(initialize: bool = False) -> str:
    """Stable backend key for baselines / tile tables ("cpu", "tpu-v5e").

    With ``initialize=False`` (default) and jax not yet initialized,
    the key is inferred from the configured env (JAX_PLATFORMS /
    REPRO_PLATFORM, default "cpu") so numpy-only benchmark runs never
    pay jax startup just to label their artifact.
    """
    if jax_is_initialized() or initialize:
        return backend_info().key
    plat = os.environ.get("JAX_PLATFORMS") \
        or os.environ.get("REPRO_PLATFORM") or "cpu"
    plat = plat.split(",")[0].strip() or "cpu"
    if plat == "cpu":
        return "cpu"
    return _PLATFORM_DEFAULT_HW.get(plat, plat)


# --------------------------------------------------------------------------
# persistent compilation cache
# --------------------------------------------------------------------------

_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Called by the mains (launchers, benchmark runner, chip smoke), never
    on import.  ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads
    it itself, and nothing else is set); otherwise the cache lives at
    ``<checkout>/.jax_cache``, a fixed path, so the next process of this
    checkout finds it.  Returns the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
