"""Architecture configuration.

One dataclass covers the whole assigned pool: dense / MoE / hybrid
(RG-LRU + local attention) / SSM (RWKV6) / encoder-decoder (Whisper) /
VLM-backbone.  Exact dimension sets live in repro/configs/<arch>.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["MoEConfig", "YarnConfig", "ArchConfig", "reduce_for_smoke"]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0          # always-on shared experts (fine-grained MoE)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # 'ep'  : shard experts across the model axis (E % axis == 0)
    # 'tp'  : shard each expert's d_ff across the model axis
    expert_shard: str = "ep"
    # 'global'  : capacity dispatch over the whole (sharded) batch — one
    #             global sort; GSPMD materializes replicated [E, C, d]
    #             buffers (the paper-faithful naive port; baseline).
    # 'grouped' : per-sequence dispatch (vmapped over batch) — sort,
    #             gather and scatter stay local to the data shard; the
    #             Sec-Perf optimization (EXPERIMENTS.md).
    # 'dropless': every held expert runs on every token, gated (no
    #             capacity), so a row's output and aux loss depend on
    #             that row alone; the only dispatch CodedTrainer takes
    #             (docs/architecture.md §2.1).  'global' and 'grouped'
    #             serve the non-coded callers (ROADMAP 1.6).
    dispatch: str = "global"
    # Pad expert STORAGE to this count with zero-routed dummy experts so
    # the expert dim divides the 'model' axis (granite: 40 -> 48 on a
    # 16-way axis => clean EP; Sec-Perf iteration 2).  0 = no padding.
    pad_experts_to: int = 0
    # (first, count): the experts stored on this device, one expert-
    # parallel share; the router still scores all num_experts.  None =
    # all of them.  A partial share needs dispatch='dropless'.
    held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.held is not None:
            first, count = map(int, self.held)
            object.__setattr__(self, "held", (first, count))
            if not (0 <= first and count > 0
                    and first + count <= self.num_experts):
                raise ValueError(f"held {self.held} outside the "
                                 f"{self.num_experts} experts")
            if self.dispatch != "dropless":
                raise ValueError("a share of the experts (held=) needs "
                                 "dispatch='dropless'")
        if self.dispatch not in ("global", "grouped", "dropless"):
            raise ValueError(f"dispatch {self.dispatch!r}")

    @property
    def e_padded(self) -> int:
        return max(self.pad_experts_to, self.num_experts)

    @property
    def held_range(self) -> Tuple[int, int]:
        """(first, count) of the experts stored here."""
        return self.held if self.held is not None else (0, self.e_padded)


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (the ``rope_type: yarn`` settings of a Hugging
    Face config); ``layers.yarn_inv_freq`` computes the frequencies."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None   # None: 0.1 ln(factor) + 1


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0              # 0 -> d_model // n_heads

    # --- attention details ---
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    local_window: int = 0        # 0 = global; >0 = sliding-window attention
    logit_softcap: float = 0.0

    # --- block composition ---
    # repeating pattern of block kinds; "attn" | "rec" (RG-LRU) | "rwkv"
    block_pattern: Tuple[str, ...] = ("attn",)
    # per position of block_pattern: the attention window (0 = global)
    # and the rotary kind ("default" | "yarn"); () = local_window and
    # plain RoPE in every layer
    window_pattern: Tuple[int, ...] = ()
    rope_pattern: Tuple[str, ...] = ()
    yarn: Optional[YarnConfig] = None
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    act: str = "swiglu"          # swiglu | geglu | gelu
    tie_embeddings: bool = False

    # --- family extras ---
    moe: Optional[MoEConfig] = None
    encoder_layers: int = 0      # >0 -> encoder-decoder
    frontend: str = "embed"      # embed | frames (audio stub) | patches (vlm stub)
    frontend_tokens: int = 0     # prefix length fed by the stub frontend
    rnn_width: int = 0           # RG-LRU recurrence width (0 -> d_model)
    conv_width: int = 4          # temporal conv kernel in recurrent blocks

    # --- distribution overrides (see dist.sharding.rules_for) ---
    batch_shard_model: bool = False  # attn-free: 'model' axis as extra DP
    fsdp_params: bool = False        # shard a replicated param dim on 'data'

    # --- numerics / runtime ---
    param_dtype: str = "float32"
    norm_io: str = "f32"         # f32 | bf16: dtype of norm outputs (fp32
                                 # reduction internals either way)
    loss_chunk: int = 0          # >0: head+CE in seq chunks (no full
                                 # [B,S,V] fp32 materialization)
    compute_dtype: str = "bfloat16"
    remat: str = "dots"          # none | dots | full
    scan_layers: bool = True
    attn_impl: str = "xla_chunked"   # xla_chunked | xla_naive | pallas | pallas_interpret
    seq_impl: str = "auto"           # recurrence impl: auto | scan | chunked
    vocab_pad_to: int = 256

    # --- optimizer schedule hint (minicpm uses WSD) ---
    schedule: str = "cosine"

    def __post_init__(self):
        # JSON gives lists and dicts: the frozen, hashable forms
        for key in ("block_pattern", "window_pattern", "rope_pattern"):
            object.__setattr__(self, key, tuple(getattr(self, key)))
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoEConfig(**self.moe))
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", YarnConfig(**self.yarn))
        for key in ("window_pattern", "rope_pattern"):
            if getattr(self, key) and \
                    len(getattr(self, key)) != len(self.block_pattern):
                raise ValueError(f"{self.name}: {key} must match "
                                 f"block_pattern's {len(self.block_pattern)}")
        if "yarn" in self.rope_pattern and self.yarn is None:
            raise ValueError(f"{self.name}: rope_pattern names yarn, but "
                             f"no yarn settings are given")
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))
        if self.n_heads and self.n_kv and self.n_heads % self.n_kv:
            raise ValueError(f"{self.name}: n_heads {self.n_heads} % n_kv {self.n_kv}")
        if self.family in ("encdec",) and self.encoder_layers <= 0:
            raise ValueError("encdec family needs encoder_layers > 0")

    # ----- derived sizes -----
    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab, self.vocab_pad_to)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv * self.d_head

    @property
    def d_rnn(self) -> int:
        return self.rnn_width or self.d_model

    def window_at(self, i: int) -> int:
        """Attention window of pattern position ``i`` (0 = global)."""
        return self.window_pattern[i] if self.window_pattern \
            else self.local_window

    def yarn_at(self, i: int) -> Optional[YarnConfig]:
        """YaRN settings of pattern position ``i``; None = plain RoPE."""
        if self.rope_pattern and self.rope_pattern[i] == "yarn":
            return self.yarn
        return None

    @property
    def pattern_counts(self) -> Tuple[int, int]:
        """(n_full_pattern_groups, n_remainder_layers)."""
        p = len(self.block_pattern)
        return self.n_layers // p, self.n_layers % p

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, dff, V = self.d_model, self.d_ff, self.padded_vocab
        emb = V * d
        head = 0 if self.tie_embeddings else V * d
        per_attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.qkv_bias:
            per_attn += self.q_dim + 2 * self.kv_dim
        n_up = 2 if self.act in ("swiglu", "geglu") else 1
        per_mlp = (n_up + 1) * d * dff
        per_moe = 0
        if self.moe is not None:
            m = self.moe
            held = m.held[1] if m.held is not None else m.num_experts
            per_moe = (held + m.num_shared) * (n_up + 1) * d * m.d_ff_expert \
                + d * m.num_experts
        per_rec = 0
        if "rec" in self.block_pattern:
            dr = self.d_rnn
            per_rec = 2 * d * dr + dr * d + self.conv_width * dr + 2 * dr * (dr // 8) + dr
        total_blocks = 0
        counts = self._block_counts()
        for kind, cnt in counts.items():
            if kind == "attn":
                total_blocks += cnt * (per_attn + (per_moe if self.moe else per_mlp) + 2 * d)
            elif kind == "rec":
                total_blocks += cnt * (per_rec + per_mlp + 2 * d)
            elif kind == "rwkv":
                # time-mix (5 proj + decay lora) + channel-mix
                tm = 4 * d * d + d * d + 2 * d * 64
                cm = 2 * d * self.d_ff
                total_blocks += cnt * (tm + cm + 2 * d)
        if self.encoder_layers:
            enc = self.encoder_layers * (per_attn + per_mlp + 2 * d)
            dec_cross = self.n_layers * (per_attn + d)  # cross-attn blocks
            total_blocks += enc + dec_cross
        return emb + head + total_blocks + d  # final norm

    def _block_counts(self) -> dict:
        groups, rem = self.pattern_counts
        counts: dict = {}
        for kind in self.block_pattern:
            counts[kind] = counts.get(kind, 0) + groups
        for kind in self.block_pattern[:rem]:
            counts[kind] = counts.get(kind, 0) + 1
        return counts

    @property
    def supports_long_context(self) -> bool:
        """True iff decode cost is sub-quadratic in context (SSM / hybrid
        with bounded window) — gates the long_500k shape per the brief."""
        kinds = set(self.block_pattern)
        if "rwkv" in kinds and "attn" not in kinds:
            return True
        if "rec" in kinds:
            return self.local_window > 0  # bounded KV per attn layer
        return False


def reduce_for_smoke(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests."""
    pat = len(cfg.block_pattern)
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe, num_experts=min(cfg.moe.num_experts, 4),
            top_k=min(cfg.moe.top_k, 2), d_ff_expert=32,
            num_shared=min(cfg.moe.num_shared, 1), held=None,
            # no capacity drops at smoke scale, so cached decode is exactly
            # parity with the full forward (drops are batch-dependent)
            capacity_factor=8.0)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=max(2 * pat, pat),         # at least 2 pattern groups
        d_model=64,
        n_heads=4,
        n_kv=min(cfg.n_kv, 2) if cfg.n_kv < cfg.n_heads else 4,
        d_head=16,
        d_ff=128,
        vocab=503,                           # deliberately non-multiple of 256
        moe=moe,
        encoder_layers=2 if cfg.encoder_layers else 0,
        frontend_tokens=8 if cfg.frontend != "embed" else 0,
        local_window=min(cfg.local_window, 16) if cfg.local_window else 0,
        window_pattern=tuple(min(w, 16) for w in cfg.window_pattern),
        rnn_width=64 if cfg.rnn_width else 0,
        param_dtype="float32",
        compute_dtype="float32",
        remat="none",
        scan_layers=cfg.scan_layers,
        vocab_pad_to=64,
    )
