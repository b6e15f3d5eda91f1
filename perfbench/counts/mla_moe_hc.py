"""Operations and bytes that the latent-attention, sparse-expert decoder
needs, from shapes.  As ``counts/gpt2.py``: what the algorithm needs,
whatever implements it (no padding, no copies, causal attention at half
the square, a token through its chosen experts only).
"""


def attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk \
        + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                     + cfg["v_head_dim"]) \
        + h * cfg["v_head_dim"] * d


def norm_params(cfg: dict) -> int:
    """The gains of one layer's RMSNorms (two sublayers, two low ranks)."""
    return 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def mixing_params(cfg: dict) -> int:
    """One layer's two stream mixers: the norm's gain over n d, Phi
    (n d x (2 n + n^2)), three gates and the biases."""
    n = cfg["hc_mult"]
    width = 2 * n + n * n
    return 2 * (n * cfg["hidden_size"] * (width + 1) + 3 + width)


def expert_params(cfg: dict) -> int:
    """One routed expert (as many in the shared one a width)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) + norm_params(cfg) + mixing_params(cfg) \
        + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layer_params(cfg: dict, held=None) -> int:
    held = cfg["n_routed_experts"] if held is None else held
    return attention_params(cfg) + norm_params(cfg) + mixing_params(cfg) \
        + router_params(cfg) \
        + (held + cfg["n_shared_experts"]) * expert_params(cfg)


def vocabulary_params(cfg: dict) -> int:
    """The embedding, the untied head and the final norm."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"] + cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    dense = cfg["first_k_dense_replace"]
    return dense * dense_layer_params(cfg) \
        + (cfg["num_hidden_layers"] - dense) * expert_layer_params(cfg) \
        + vocabulary_params(cfg)


def active_layer_params(cfg: dict, dense: bool) -> int:
    """Parameters of one layer that take part in a product for a token."""
    shared = attention_params(cfg) + mixing_params(cfg)
    if dense:
        return shared + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return shared + router_params(cfg) \
        + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) \
        * expert_params(cfg)


def active_body_params(cfg: dict) -> int:
    dense = cfg["first_k_dense_replace"]
    return dense * active_layer_params(cfg, True) \
        + (cfg["num_hidden_layers"] - dense) \
        * active_layer_params(cfg, False)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def active_params(cfg: dict) -> int:
    """A token's: the layers' and the head (the embedding is a gather)."""
    return active_body_params(cfg) + head_params(cfg)


def latent_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """What one cached token holds in one layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    """One cached token, all layers."""
    return float(cfg["num_hidden_layers"]
                 * latent_bytes_per_token_layer(cfg, bytes_per_value))


def absorbed_flops_per_context_token_layer(cfg: dict) -> int:
    """The absorbed step against one cached token in one layer: scores
    over latent + rotary, values over the latent."""
    h = cfg["num_attention_heads"]
    return 2 * h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + 2 * h * cfg["kv_lora_rank"]


def non_expert_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """What a decode step reads whatever it routes: everything but the
    routed experts and the embedding table."""
    routed = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * cfg["n_routed_experts"] * expert_params(cfg)
    return float(total_params(cfg) - routed - head_params(cfg)) \
        * bytes_per_param


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """What a decode step that touches every expert reads of the weights."""
    return float(total_params(cfg) - head_params(cfg)) * bytes_per_param


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    return float(expert_params(cfg)) * bytes_per_param


def expert_flops_per_assignment(cfg: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(cfg)


def decode_flops(cfg: dict, context: int) -> float:
    """One new token against ``context`` cached ones."""
    return 2.0 * active_params(cfg) + cfg["num_hidden_layers"] \
        * absorbed_flops_per_context_token_layer(cfg) * float(context)


def attention_fwd_flops(cfg: dict, t: int) -> float:
    """Expanded causal attention over ``t`` tokens, all layers: QK^T over
    nope + rope and PV over v, halved by the mask."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * 1.0 * t * t * h \
        * (qk + cfg["v_head_dim"])


def prefill_flops(cfg: dict, t: int) -> float:
    """A prompt of ``t`` tokens (the head is applied to its last only)."""
    return 2.0 * active_body_params(cfg) * t + 2.0 * head_params(cfg) \
        + attention_fwd_flops(cfg, t)
