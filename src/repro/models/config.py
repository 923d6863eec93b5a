"""Unified architecture config for every assigned model family."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DSAConfig:
    """DeepSeek Sparse Attention decode config (the paper's setting)."""
    enabled: bool = True
    k: int = 2048                   # Top-K selection size
    indexer_heads: int = 64         # H in Eq. 1
    indexer_dim: int = 128          # d_i
    min_n: int = 4096               # dense decode below this cache length
    selector: str = "auto"          # auto | gvr | radix | exact | sp_gvr
    max_candidates: int = 6144      # C (MAX_CANDIDATES)
    gate_max_n: int = 200_000       # paper's canUseHeuristic N bound


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0            # experts held here (all of them unless
    top_k: int = 2                  # a share is stated below)
    expert_d_ff: int = 0
    capacity_factor: float = 1.25
    # an expert share (expert parallelism): the router scores all
    # `total_experts` (0: as many as are held) and this chip holds
    # experts [first_expert, first_expert + num_experts)
    total_experts: int = 0
    first_expert: int = 0
    shared_d_ff: int = 0            # one shared expert of this width (0: none)
    # DeepSeek's noaux_tc routing: sigmoid scores plus a router bias choose
    # top_k experts within the `topk_group` best of `n_group` groups, and
    # the gates are the chosen sigmoid scores normalised to sum 1, times
    # `routed_scaling`. The MLA expert layer alone reads the share, the
    # shared expert and these three; other expert layers route by a softmax
    # over the top_k router logits (ModelConfig refuses them elsewhere)
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0

    @property
    def routed_experts(self) -> int:
        """The experts the router scores (the published count)."""
        return self.total_experts or self.num_experts


@dataclasses.dataclass(frozen=True)
class YaRNConfig:
    """YaRN RoPE scaling (DeepSeek-V3's `rope_scaling`). Its `mscale`
    scales cos and sin by mscale(mscale) / mscale(mscale_all_dim), which is
    1 where the two are equal, as in every DeepSeek-V3 release: only
    `mscale_all_dim` is kept, for the softmax scale."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    rope_kind: str = "rope"         # rope | rope2d | mrope
    rope_base: float = 10000.0
    rope_fraction: float = 1.0      # chatglm applies RoPE to half the dims
    swa_window: Optional[int] = None
    moe: MoEConfig = MoEConfig()
    dsa: DSAConfig = DSAConfig()
    # hybrid (jamba): one attention layer every `attn_every` layers
    attn_every: int = 0             # 0 = all-attention
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # rwkv6
    rwkv_head_dim: int = 64
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 1500      # stubbed conv frontend output length
    # vlm (qwen2-vl)
    num_patches: int = 0            # stubbed patch embedding prefix length
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    # multi-head latent attention (DeepSeek-V3): queries through a
    # `q_lora_rank` latent, keys and values through one `kv_lora_rank`
    # latent per token plus a `qk_rope_head_dim` RoPE key shared by every
    # head. kv_lora_rank > 0 selects it, with two layer stacks: the
    # `first_k_dense` leading layers with a dense MLP of width d_ff, then
    # expert layers (`moe`)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense: int = 0
    yarn: Optional[YaRNConfig] = None   # rope_kind "yarn"

    def __post_init__(self):
        if self.is_mla:
            return
        moe, plain = self.moe, MoEConfig()
        mla_only = [k for k in ("total_experts", "first_expert", "shared_d_ff",
                                "n_group", "topk_group", "routed_scaling")
                    if getattr(moe, k) != getattr(plain, k)]
        if self.yarn is not None or self.rope_kind == "yarn":
            mla_only.append("yarn")
        if mla_only:
            raise ValueError(
                f"{self.name}: {', '.join(mla_only)} are read by the "
                f"multi-head latent attention model alone (kv_lora_rank > 0)")

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def _mla_counts(self) -> Tuple[int, int]:
        """(parameters, parameters a token uses) of an MLA model: the
        latent projections, the indexer with its per-token head weights,
        the leading dense layers, then expert layers with their router
        (as wide as the routed experts), shared expert and held experts."""
        d, h, v = self.d_model, self.n_heads, self.vocab
        ql, kvl = self.q_lora_rank, self.kv_lora_rank
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        attn = (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
                + kvl * h * (nope + vd) + h * vd * d)
        if self.dsa.enabled:
            hi, di = self.dsa.indexer_heads, self.dsa.indexer_dim
            attn += ql * hi * di + d * di + d * hi
        moe = self.moe
        expert = 3 * d * moe.expert_d_ff
        outer = (attn + d * moe.routed_experts + moe.routed_experts
                 + 3 * d * moe.shared_d_ff)
        nd, nm = self.first_k_dense, self.n_layers - self.first_k_dense
        dense = nd * (attn + 3 * d * self.d_ff)
        emb = v * d * (1 if self.tie_embeddings else 2)
        total = emb + dense + nm * (outer + moe.num_experts * expert)
        # a token's top_k experts lie among the held ones held/routed of
        # the time
        used = moe.top_k * min(moe.num_experts / moe.routed_experts, 1.0)
        return total, int(emb + dense + nm * (outer + used * expert))

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        if self.is_mla:
            return self._mla_counts()[0]
        d, f, v, l = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.hd
        emb = v * d * (1 if self.tie_embeddings else 2)
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.moe.num_experts:
            ff = self.moe.num_experts * 3 * d * self.moe.expert_d_ff + d * self.moe.num_experts
        else:
            ff = 3 * d * f
        if self.family == "ssm":
            di = d * self.mamba_expand
            blocks = l * (2 * d * di + di * d + 2 * d * f)   # rough rwkv blocks
        elif self.attn_every:
            # hybrid (jamba): MoE on odd layers, dense FFN on even (1:1 split)
            n_attn = l // self.attn_every
            n_mamba = l - n_attn
            di = d * self.mamba_expand
            dtr = max(d // 16, 1)
            mamba = (2 * d * di + di * (dtr + 2 * self.mamba_d_state)
                     + dtr * di + di * d)
            moe_ff = self.moe.num_experts * 3 * d * self.moe.expert_d_ff
            dense_ff = 3 * d * f
            blocks = (n_attn * attn + n_mamba * mamba
                      + (l // 2) * moe_ff + (l // 2) * dense_ff)
        else:
            blocks = l * (attn + ff)
        if self.dsa.enabled and not self.is_attention_free:
            blocks += l * (d * self.dsa.indexer_heads * self.dsa.indexer_dim
                           + d * self.dsa.indexer_dim)
        if self.encoder_layers:
            blocks += self.encoder_layers * (attn + ff) + l * attn  # cross-attn
        return emb + blocks

    def active_param_count(self) -> int:
        """Active params per token (MoE top-k instead of all experts)."""
        if self.is_mla:
            return self._mla_counts()[1]
        if not self.moe.num_experts:
            return self.param_count()
        d, l = self.d_model, self.n_layers
        n_moe = l // 2 if self.attn_every else l   # hybrid: MoE every 2nd layer
        full = self.param_count()
        all_ff = n_moe * self.moe.num_experts * 3 * d * self.moe.expert_d_ff
        act_ff = n_moe * self.moe.top_k * 3 * d * self.moe.expert_d_ff
        return full - all_ff + act_ff
