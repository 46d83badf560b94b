"""Model/config schema shared by all architectures.

A model is a stack of `num_layers` layers; `layer_pattern` describes one
repeating period as `"<mixer>+<ff>"` entries:

  mixers: attn (GQA+RoPE) | mla | xattn (cross-attention) | mamba
          | mlstm | slstm
  ff:     dense (SwiGLU) | moe | none

The stack scans over `(num_layers - first_dense_layers) / len(layer_pattern)`
periods with stacked parameters, which keeps the lowered HLO compact for
40–72-layer models.  `first_dense_layers` leading blocks (the pattern's
first mixer with a dense FFN, DeepSeek's `first_k_dense_replace`) run
before the scanned periods.
"""
from __future__ import annotations

import dataclasses
import jax.numpy as jnp

from repro.core.bramac_linear import QuantConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | hybrid | vlm | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    layer_pattern: tuple[str, ...] = ("attn+dense",)
    head_dim: int | None = None      # default d_model // num_heads
    first_dense_layers: int = 0      # leading "<mixer>+dense" blocks

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim (0 → d_ff)
    router_experts: int = 0          # experts the router scores (0 →
    #                                  num_experts); with more than
    #                                  num_experts this layer holds the
    #                                  share [first_expert, first_expert +
    #                                  num_experts) of an expert-parallel
    #                                  deployment and computes that part
    first_expert: int = 0
    n_shared_experts: int = 0        # always-on SwiGLU of width
    #                                  n_shared_experts * expert_d_ff
    norm_topk_prob: bool = True      # renormalise the top-k probabilities
    moe_dispatch: str = "sort"       # "sort" (default) | "cumsum"
    #   sort: argsort-based rank-in-expert, O(T·k log T·k), no E-wide
    #   temporaries — adopted as default after the §Perf hillclimb;
    #   cumsum: the original (T·k, E) one-hot cumsum — O(T·E) memory and
    #   quadratic-cost reduce-window lowering at 32k-token scale.  The
    #   §Perf baselines in EXPERIMENTS.md were recorded with "cumsum".
    moe_capacity_factor: float = 1.25
    ep_dispatch: str = "global"      # "global" | "per_source"
    #   global: exact global-capacity buffers (all_gather combine);
    #   per_source: GShard-style per-source capacity C_src = ceil(C/n) with
    #   a mirrored all_to_all combine — lossy fast path, drops decided
    #   shard-locally (see repro.parallel.ep).

    # --- attention ---
    rope_theta: float = 10_000.0
    # YaRN scaling of the MLA rope dims (DeepSeek-V2 `rope_scaling`); a
    # factor of 1 is plain RoPE
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_interleaved: bool = False   # MLA rope rotates pairs (2i, 2i+1),
    #                                  not the two halves
    q_lora_rank: int = 0             # MLA
    kv_lora_rank: int = 0            # MLA
    qk_nope_dim: int = 64            # MLA per-head dims
    qk_rope_dim: int = 32
    v_head_dim: int = 64

    # --- mamba ---
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0           # 0 → ceil(d_model / 16)

    # --- xlstm ---
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    chunk_size: int = 256            # chunkwise scan for mamba/mlstm

    # --- modality frontends (stubs per assignment) ---
    vision_tokens: int = 0           # precomputed patch embeddings (vlm)
    audio_frontend: bool = False     # precomputed frame embeddings (audio)

    # --- execution ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    quant: QuantConfig = QuantConfig(enabled=False)
    quant_kv: bool = False           # int8 KV cache (GQA decode; §Perf)
    page_size: int = 16              # KV-cache page rows ("BRAM-array-sized"
    #                                  blocks): the paged serving layout
    #                                  allocates the cache as a shared pool
    #                                  of fixed (page_size,)-row pages with
    #                                  per-slot block tables instead of a
    #                                  dense [slot, max_seq] reservation
    remat: bool = True
    scan_layers: bool = True         # False: unroll periods (exact HLO cost
    #                                  accounting — scan bodies are counted
    #                                  once by XLA cost analysis)
    logical_rules: str = "default"   # sharding rule set name

    def __post_init__(self):
        if (self.num_layers - self.first_dense_layers) \
                % len(self.layer_pattern):
            raise ValueError(
                f"{self.name}: num_layers {self.num_layers} not divisible by "
                f"pattern period {len(self.layer_pattern)}")
        if self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError(f"{self.name}: heads/kv_heads mismatch")
        if self.page_size < 1:
            raise ValueError(f"{self.name}: page_size must be >= 1, "
                             f"got {self.page_size}")

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def n_periods(self) -> int:
        return (self.num_layers - self.first_dense_layers) \
            // len(self.layer_pattern)

    @property
    def dense_spec(self) -> str:
        """Block spec of the leading dense layers."""
        return self.layer_pattern[0].split("+")[0] + "+dense"

    @property
    def n_router_experts(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter count (for roofline MODEL_FLOPS and memory budgets) ----
    def param_count(self) -> int:
        return sum(_layer_params(self, spec) for spec in self.layer_pattern) \
            * self.n_periods + 2 * self.vocab_size * self.d_model \
            + self.first_dense_layers * _layer_params(self, self.dense_spec)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only) — the N in
        MODEL_FLOPS = 6·N_active·D."""
        if not self.num_experts:
            return self.param_count()
        total = 2 * self.vocab_size * self.d_model \
            + self.first_dense_layers * _layer_params(self, self.dense_spec)
        for spec in self.layer_pattern:
            n = _layer_params(self, spec)
            if spec.endswith("+moe"):
                full_moe = self.num_experts * 3 * self.d_model \
                    * self.expert_d_ff
                active_moe = self.experts_per_token * 3 * self.d_model \
                    * self.expert_d_ff
                n = n - full_moe + active_moe
            total += n * self.n_periods
        return total


def _layer_params(cfg: ModelConfig, spec: str) -> int:
    mixer, ff = spec.split("+")
    d = cfg.d_model
    n = 0
    if mixer in ("attn", "xattn"):
        n += d * cfg.num_heads * cfg.hd + d * cfg.hd * cfg.num_kv_heads * 2 \
            + cfg.num_heads * cfg.hd * d
    elif mixer == "mla":
        q = cfg.num_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
        n += d * cfg.q_lora_rank + cfg.q_lora_rank * q if cfg.q_lora_rank \
            else d * q
        n += d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
        n += cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_dim + cfg.v_head_dim)
        n += cfg.num_heads * cfg.v_head_dim * d
    elif mixer == "mamba":
        d_in = cfg.mamba_expand * d
        dt_rank = cfg.mamba_dt_rank or -(-d // 16)
        n += d * 2 * d_in + d_in * cfg.mamba_d_conv \
            + d_in * (dt_rank + 2 * cfg.mamba_d_state) + dt_rank * d_in \
            + d_in * cfg.mamba_d_state + d_in + d_in * d
    elif mixer == "mlstm":
        dp = int(cfg.mlstm_proj_factor * d)
        n += d * 2 * dp + 3 * dp * dp // max(cfg.num_heads, 1) + dp * d \
            + 2 * dp  # qkv (blockwise), gates, out
    elif mixer == "slstm":
        dp = int(cfg.slstm_proj_factor * d)
        n += 4 * d * d + 2 * d * dp + dp * d
    if ff == "dense":
        n += 3 * d * cfg.d_ff
    elif ff == "moe":
        n += (cfg.num_experts + cfg.n_shared_experts) * 3 * d \
            * cfg.expert_d_ff + d * cfg.n_router_experts
    return n
