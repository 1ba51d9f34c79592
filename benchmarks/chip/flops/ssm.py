"""Model FLOPs per trained token of a Mamba-1 decoder (forward and
backward, nothing recomputed counted): 6 x the matmul params (in, x, dt
and out projections of every layer, and the head; not the embedding
gather), plus the selective scan's required operations: per layer and
token, 5 per (d_inner, d_state) element forward (decay x state, dt*u x B,
the add, and the C contraction's multiply-add), and the depthwise conv's
2 x kernel per channel; the backward pass takes twice the forward's."""


def matmul_params(cfg: dict) -> int:
    D, di = cfg["hidden_size"], cfg["intermediate_size"]
    ds, dr = cfg["state_size"], cfg["time_step_rank"]
    layer = D * 2 * di + di * (dr + 2 * ds) + dr * di + di * D
    return cfg["num_hidden_layers"] * layer + D * cfg["vocab_size"]


def per_token(cfg: dict, seq_len: int) -> float:
    di, ds = cfg["intermediate_size"], cfg["state_size"]
    elementwise = 5 * di * ds + 2 * cfg["conv_kernel"] * di
    return (6.0 * matmul_params(cfg)
            + 3.0 * cfg["num_hidden_layers"] * elementwise)


# configuration file key -> the program's ModelConfig field it must equal
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_inner",
                "state_size": "ssm_state", "conv_kernel": "ssm_conv",
                "time_step_rank": "dt_rank_", "expand": "ssm_expand",
                "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
                "layer_norm_epsilon": "norm_eps", "torch_dtype": "param_dtype"}
