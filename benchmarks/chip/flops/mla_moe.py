"""Model FLOPs per trained token of a DeepSeek-V3 decoder at this chip's
share (forward and backward, nothing recomputed counted): 6 x the matmul
params a token uses (latent attention's projections, the dense layers'
SwiGLU, each expert layer's router, shared experts and its held experts at
top_k x held / router_width of one expert, and the head; not the
embedding gather), plus attention's 6 x layers x heads x (qk + v head
dims) x the mean number of keys a token attends to, causally.

Also the work of the held experts' grouped matmuls in a step, from the
rows the program routed to them (``repro.obs.counters``)."""


def mean_keys(seq_len: int) -> float:
    return (seq_len + 1) / 2


def _matmul_params(cfg: dict, experts: float) -> float:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    E, F = cfg["router_width"], cfg["moe_intermediate_size"]
    attn = (D * H * (nope + rope) + D * (r + rope) + r * H * (nope + dv)
            + H * dv * D)
    dense = 3 * D * cfg["intermediate_size"]
    moe = D * E + 3 * D * F * cfg["n_shared_experts"] + experts * 3 * D * F
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return L * attn + Ld * dense + (L - Ld) * moe + D * V


def matmul_params(cfg: dict) -> float:
    """The matmul parameters a token uses, the held experts at their
    expected share of its top_k."""
    return _matmul_params(cfg, cfg["num_experts_per_tok"]
                          * cfg["n_routed_experts"] / cfg["router_width"])


def held_matmul_params(cfg: dict) -> int:
    """The matmul parameters this chip holds: every held expert whole."""
    return int(_matmul_params(cfg, cfg["n_routed_experts"]))


def per_token(cfg: dict, seq_len: int) -> float:
    qk_v = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
    attn = (6 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * qk_v * mean_keys(seq_len))
    return 6.0 * matmul_params(cfg) + attn


def grouped_matmul_flops(rows: float, d_model: int, d_expert: int,
                         passes: int) -> float:
    """The held experts' three matmuls (gate, up, down) over ``rows``
    routed rows, each run ``passes`` times a step (forward, its recompute
    under remat, and the two backward products)."""
    return 2.0 * rows * d_model * d_expert * 3 * passes


def grouped_matmul_bytes(rows: float, d_model: int, d_expert: int,
                         experts_held: int, passes: int,
                         itemsize: int = 2) -> float:
    """Bytes those matmuls must move at least: for each call, the routed
    rows' operand and result and the held experts' weights, in bf16."""
    per_call = (rows * (d_model + d_expert)
                + experts_held * d_model * d_expert)
    return float(itemsize) * per_call * 3 * passes


# configuration file key -> the program's ModelConfig field it must equal
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "moe_intermediate_size": "d_expert_",
                "num_attention_heads": "n_heads",
                "kv_lora_rank": "kv_lora_rank",
                "qk_nope_head_dim": "qk_nope_dim",
                "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_hd",
                "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
                "router_width": "n_experts",
                "n_routed_experts": "experts_held_",
                "num_experts_per_tok": "top_k",
                "n_shared_experts": "n_shared_experts",
                "routed_scaling_factor": "routed_scale",
                "first_k_dense_replace": "first_dense",
                "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
                "torch_dtype": "param_dtype"}
