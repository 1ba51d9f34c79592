"""Model configuration for every architecture family in the zoo.

One dataclass covers dense / MoE / SSM / hybrid / enc-dec / VLM backbones so the
HETHUB planner, sharding rules and launch layer can treat all architectures
uniformly (the planner only consumes per-layer costs derived from these dims).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # --- attention options ---
    qk_norm: bool = False
    # multi-head latent attention (DeepSeek-V2/V3) when kv_lora_rank > 0:
    # q (no q LoRA) and a normed kv latent of this rank, per-head k_nope/v
    # up-projected from it, one rotary key (qk_rope_dim) shared by all heads
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    window: Optional[int] = None           # sliding-window size (SWA) or None
    rope_theta: float = 10000.0
    attn_logit_softcap: Optional[float] = None

    # --- MLP ---
    act: str = "swiglu"                    # swiglu | sq_relu | gelu | geglu

    # --- MoE ---
    n_experts: int = 0                     # experts the router scores
    top_k: int = 0
    capacity_factor: float = 1.25
    # sigmoid routing (DeepSeek-V3 ``noaux_tc``): top_k of score + a
    # selection bias that balances the experts' load, the chosen scores
    # normalised and scaled; dropless grouped-matmul dispatch over the
    # first ``experts_held`` experts, which this chip holds (0 = all),
    # shared experts added once, and the first ``first_dense`` layers dense
    # (width d_ff) before the expert layers
    router: str = "softmax"                # softmax | sigmoid
    routed_scale: float = 1.0
    d_expert: int = 0                      # 0 -> d_ff
    n_shared_experts: int = 0
    experts_held: int = 0
    first_dense: int = 0

    # --- SSM (mamba-1) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0                       # 0 -> ceil(d_model / 16)

    # --- hybrid (recurrentgemma): block pattern, cycled over layers ---
    block_pattern: Tuple[str, ...] = ()    # e.g. ("rec", "rec", "attn")
    lru_width: int = 0                     # 0 -> d_model

    # --- enc-dec (whisper) ---
    n_encoder_layers: int = 0              # decoder layers = num_layers

    # --- VLM ---
    n_vision_tokens: int = 0               # stub frontend: precomputed embeds

    # --- numerics / implementation ---
    param_dtype: str = "bfloat16"
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    attn_chunk: int = 0                    # 0 = unchunked; else q-block size
    remat: bool = True
    remat_policy: str = ""          # "" = full remat; save_proj = selective
    moe_impl: str = "gspmd"         # gspmd | shard_map (manual SP boundary)
    loss_chunk: int = 0             # CE over seq chunks (big-vocab memory)
    scan_layers: bool = True
    cache_update: str = "dus"              # dus | onehot (seq-sharded caches)
    # sequence-parallel activation constraint applied at block boundaries,
    # e.g. (("data",), "model", None): stored scan carries shard their seq
    # dim over TP ranks (Megatron SP) — memory-roofline lever
    act_sharding: tuple = ()
    # (dp_axes_tuple, tp_axis) mesh hints for layers that need explicit
    # constraints (MoE dispatch buffers); empty = no constraints (CPU tests)
    mesh_axes: tuple = ()
    # constraint on x entering the LM head (FSDP: reshard batch from
    # (data, model) back to data-only so the vocab-parallel CE stays local)
    head_act_sharding: tuple = ()

    # ---- derived ----
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_hd(self) -> int:
        """Width of a query/key head: the scores' contraction."""
        return self.qk_nope_dim + self.qk_rope_dim if self.mla else self.hd

    @property
    def v_hd(self) -> int:
        return self.v_head_dim if self.mla else self.hd

    @property
    def d_expert_(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def experts_held_(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def sigmoid_moe(self) -> bool:
        return self.n_experts > 0 and self.router == "sigmoid"

    def is_moe_layer(self, i: int) -> bool:
        return self.n_experts > 0 and i >= self.first_dense

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def lru_width_(self) -> int:
        return self.lru_width or self.d_model

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind, resolving the hybrid pattern."""
        if self.family == "ssm":
            return ("ssm",) * self.num_layers
        if self.family == "hybrid":
            pat = self.block_pattern or ("rec", "rec", "attn")
            return tuple(pat[i % len(pat)] for i in range(self.num_layers))
        return ("attn",) * self.num_layers

    # ---- parameter counting (for 6*N*D roofline yardstick) ----
    def attn_params(self) -> int:
        """Projection parameters of one attention layer (no norms)."""
        D, H = self.d_model, self.n_heads
        if self.mla:
            r, rope = self.kv_lora_rank, self.qk_rope_dim
            return (D * H * self.qk_hd + D * (r + rope)
                    + r * H * (self.qk_nope_dim + self.v_hd)
                    + H * self.v_hd * D)
        Hk, hd = self.n_kv_heads, self.hd
        return D * H * hd + 2 * D * Hk * hd + H * hd * D

    def attn_flops(self, kv_len: float) -> float:
        """Score and value FLOPs per query token against ``kv_len`` keys."""
        return 2.0 * self.n_heads * (self.qk_hd + self.v_hd) * kv_len

    def mlp_params(self, i: int, active_only: bool = False) -> float:
        """Parameters of layer ``i``'s MLP or expert layer, router
        included; ``active_only``: those one token uses on this chip (the
        held experts' expected share of its top_k)."""
        D, F = self.d_model, self.d_ff
        mats = 3 if self.act in ("swiglu", "geglu") else 2
        if not self.is_moe_layer(i):
            return mats * D * F
        if not self.sigmoid_moe:
            e = self.top_k if active_only else self.n_experts
            return e * mats * D * F + D * self.n_experts
        E, held = self.n_experts, self.experts_held_
        e = self.top_k * held / E if active_only else held
        shared = mats * D * self.d_expert_ * self.n_shared_experts
        return e * mats * D * self.d_expert_ + shared + D * E

    def param_count(self, active_only: bool = False) -> int:
        D, V = self.d_model, self.vocab_size
        emb = V * D * (1 if self.tie_embeddings else 2)
        kinds = self.layer_kinds()

        def ssm_p() -> int:
            di, ds, dr = self.d_inner, self.ssm_state, self.dt_rank_
            return (D * 2 * di + di * self.ssm_conv + di * (dr + 2 * ds)
                    + dr * di + di * ds + di + di * D)

        def rec_p() -> int:
            w = self.lru_width_
            return 2 * D * w + w * self.ssm_conv + 3 * w + w * D

        total = emb
        for i, k in enumerate(kinds):
            total += 2 * D  # norms
            if k == "attn":
                total += (self.attn_params() + self.kv_lora_rank
                          + self.mlp_params(i, active_only))
            elif k == "ssm":
                total += ssm_p()
            elif k == "rec":
                total += rec_p() + self.mlp_params(i, active_only)
        if self.family == "encdec":
            # encoder layers: self-attn + mlp; decoder adds cross-attn
            total += self.n_encoder_layers * (self.attn_params()
                                              + self.mlp_params(0) + 2 * D)
            total += self.num_layers * (self.attn_params() + D)
        return int(round(total))

    def flops_per_token(self, seq_len: int, active_only: bool = True) -> float:
        """Model FLOPs per token (fwd): 2*N_active*1tok + attention term."""
        n = self.param_count(active_only=active_only)
        fl = 2.0 * n
        kinds = self.layer_kinds()
        for k in kinds:
            if k == "attn":
                kv = min(seq_len, self.window) if self.window else seq_len
                fl += self.attn_flops(kv)
        return fl
