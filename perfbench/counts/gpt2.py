"""Operations and bytes that a GPT-2-shaped decoder needs, from shapes.

They count what the algorithm needs, whatever implements it: no
recomputation, no padding, no copies, causal attention at half of the
full square.  So a PR that removes padding, a copy or a recomputation
raises a share computed from these, and none can make it pass 100 %.
"""


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product for every token:
    the four attention projections, the MLP's two, and the output head
    (the embedding lookup is a gather, not a product)."""
    d, inner = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * inner) \
        + d * cfg["vocab_size"]


def attention_fwd_flops(cfg: dict, t: int) -> float:
    """Causal self-attention forward over one sequence of ``t`` tokens, all
    layers: QK^T and PV, 2 t^2 d each, halved by the causal mask."""
    return cfg["n_layer"] * 2.0 * t * t * cfg["n_embd"]


def train_flops_per_sequence(cfg: dict, t: int) -> float:
    """Forward and backward of one sequence: 6 per matmul parameter per
    token, and three times the attention's forward."""
    return 6.0 * matmul_params(cfg) * t + 3.0 * attention_fwd_flops(cfg, t)


def flash_attention_train(cfg: dict, batch: int, t: int) -> dict:
    """Forward, dQ and dK/dV kernels of one step, all layers: six products
    of t^2/2 x head_dim per head (two forward, four backward; the
    recomputed QK^T of the backward is not counted), and each of
    q, k, v, o read or written once forward and q, k, v, o, do, dq, dk, dv
    once backward, in bfloat16."""
    d = cfg["n_embd"]
    flops = cfg["n_layer"] * batch * 6.0 * t * t * d
    nbytes = cfg["n_layer"] * batch * 12.0 * t * d * 2
    return {"flops": flops, "bytes": nbytes}


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """What one decode step has to read of the weights, once."""
    return float(matmul_params(cfg)) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    """Keys and values of one cached token, all layers."""
    return 2.0 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def decode_flops(cfg: dict, context: int) -> float:
    """One new token against ``context`` cached ones."""
    return 2.0 * matmul_params(cfg) \
        + cfg["n_layer"] * 4.0 * context * cfg["n_embd"]


def prefill_flops(cfg: dict, t: int) -> float:
    """A prompt of ``t`` tokens (the head is applied to its last only)."""
    body = matmul_params(cfg) - cfg["n_embd"] * cfg["vocab_size"]
    return 2.0 * body * t + 2.0 * cfg["n_embd"] * cfg["vocab_size"] \
        + attention_fwd_flops(cfg, t)
