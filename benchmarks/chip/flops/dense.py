"""Model FLOPs per trained token of a dense decoder (forward and backward,
nothing recomputed counted): 6 x the matmul params (blocks and head, not
the embedding gather), plus attention's 12 x layers x (heads x head_dim)
x the mean number of keys a token attends to under the causal window."""


def mean_keys(seq_len: int, window) -> float:
    w = window or seq_len
    return sum(min(i + 1, w) for i in range(seq_len)) / seq_len


def matmul_params(cfg: dict) -> int:
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    layer = 2 * D * H * hd + 2 * D * Hk * hd + 3 * D * F
    return cfg["num_hidden_layers"] * layer + D * V


def per_token(cfg: dict, seq_len: int) -> float:
    attn = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * mean_keys(seq_len, cfg.get("sliding_window")))
    return 6.0 * matmul_params(cfg) + attn


# configuration file key -> the program's ModelConfig field it must equal
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
                "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
                "sliding_window": "window", "rope_theta": "rope_theta",
                "rms_norm_eps": "norm_eps", "torch_dtype": "param_dtype"}
