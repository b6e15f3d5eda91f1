"""Operations and bytes that the decoder of KDA linear-attention and
latent-attention layers over sparse experts needs, from shapes.  As
``counts/mla_moe_hc.py``: what the algorithm needs, whatever implements
it (no padding, no copies, no dead rows, causal attention at half the
square, a token through those of its chosen experts that are held here).

A KDA layer's work a token is its projections and the recurrence on its
state: three products over a ``[d_k, d_v]`` state a head (``k^T S``, the
rank-one update, ``q^T S``).  What a decode step reads of it is the state
of each live row, read and written once, and the convolution's tail.
"""


def _h(cfg: dict):
    return cfg["num_attention_heads"], cfg["head_dim"]


def kda_params(cfg: dict) -> int:
    """One KDA layer's attention: ``q, k, v``, the gate ``f``, the output
    gate ``g`` and ``o`` at ``d x H d_k`` each, ``beta``, the
    convolution, ``A_log``, ``dt_bias`` and the output norm."""
    d = cfg["hidden_size"]
    h, dk = _h(cfg)
    hd = h * dk
    return 6 * d * hd + d * h + cfg["short_conv_kernel_size"] * 3 * hd \
        + h + 2 * hd


def mla_params(cfg: dict) -> int:
    """The latent layer's attention: ``q`` with no low rank, the latent's
    down projection and norm, the up projection, the head-wise gate and
    the output."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    c, nope, rope, dv = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (c + rope) + c \
        + c * h * (nope + dv) + d * h + h * dv * d


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    return (cfg["hidden_size"] + 1) * cfg["num_experts"]


def experts_held(cfg: dict) -> int:
    return cfg.get("experts_held", (0, cfg["num_experts"]))[1]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def is_latent(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def layer_kinds(cfg: dict) -> list:
    """``(latent, dense)`` of each layer."""
    return [(is_latent(cfg, i), i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def kda_layers(cfg: dict) -> int:
    return sum(not latent for latent, _ in layer_kinds(cfg))


def latent_layers(cfg: dict) -> int:
    return sum(latent for latent, _ in layer_kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in layer_kinds(cfg))


def layer_params(cfg: dict, latent: bool, dense: bool) -> int:
    attn = mla_params(cfg) if latent else kda_params(cfg)
    ffn = dense_ffn_params(cfg) if dense else router_params(cfg) \
        + experts_held(cfg) * expert_params(cfg) + shared_params(cfg)
    return attn + ffn + 2 * cfg["hidden_size"]


def vocabulary_params(cfg: dict) -> int:
    """The embedding, the untied head and the final norm."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"] + cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    return sum(layer_params(cfg, *k) for k in layer_kinds(cfg)) \
        + vocabulary_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def held_picks(cfg: dict) -> float:
    """A token's chosen experts that are held here, in the mean (routing
    spreads a token's choices over all experts)."""
    return cfg["num_experts_per_tok"] * experts_held(cfg) / cfg["num_experts"]


def active_body_params(cfg: dict) -> float:
    """Parameters of the layers that take part in a product for a token."""
    total = 0.0
    for latent, dense in layer_kinds(cfg):
        total += mla_params(cfg) if latent else kda_params(cfg)
        total += dense_ffn_params(cfg) if dense else router_params(cfg) \
            + held_picks(cfg) * expert_params(cfg) + shared_params(cfg)
    return total


def active_params(cfg: dict) -> float:
    return active_body_params(cfg) + head_params(cfg)


def state_bytes_per_row_layer(cfg: dict) -> int:
    """One row's KDA state in one layer, float32."""
    h, dk = _h(cfg)
    return h * dk * dk * 4


def conv_bytes_per_row_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """One row's convolution tail in one KDA layer: the last ``K - 1``
    inputs of ``q``, ``k`` and ``v``."""
    h, dk = _h(cfg)
    return (cfg["short_conv_kernel_size"] - 1) * 3 * h * dk \
        * bytes_per_value


def kda_step_bytes_per_row(cfg: dict) -> float:
    """What a decode step reads and writes of one live row's recurrent
    state, all KDA layers: the state and the tail, each read and written
    once."""
    return 2.0 * kda_layers(cfg) * (state_bytes_per_row_layer(cfg)
                                    + conv_bytes_per_row_layer(cfg))


def latent_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    """One cached token, all latent layers."""
    return float(latent_layers(cfg)
                 * latent_bytes_per_token_layer(cfg, bytes_per_value))


def recurrence_flops_per_token_layer(cfg: dict) -> int:
    h, dk = _h(cfg)
    return 2 * 3 * h * dk * dk


def absorbed_flops_per_context_token_layer(cfg: dict) -> int:
    h = cfg["num_attention_heads"]
    return 2 * h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + 2 * h * cfg["kv_lora_rank"]


def non_expert_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    """What a decode step reads whatever it routes: everything but the
    routed experts and the embedding table."""
    routed = expert_layers(cfg) * experts_held(cfg) * expert_params(cfg)
    return float(total_params(cfg) - routed - head_params(cfg)) \
        * bytes_per_param


def expert_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    return float(expert_params(cfg)) * bytes_per_param


def expert_flops_per_assignment(cfg: dict) -> float:
    return 2.0 * expert_params(cfg)


def decode_flops(cfg: dict, context: int) -> float:
    """One new token against ``context`` cached ones."""
    return 2.0 * active_params(cfg) \
        + kda_layers(cfg) * recurrence_flops_per_token_layer(cfg) \
        + latent_layers(cfg) * absorbed_flops_per_context_token_layer(cfg) \
        * float(context)


def attention_fwd_flops(cfg: dict, t: int) -> float:
    """The latent layers' expanded causal attention over ``t`` tokens."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return latent_layers(cfg) * 1.0 * t * t * h * (qk + cfg["v_head_dim"])


def prefill_flops(cfg: dict, t: int) -> float:
    """A prompt of ``t`` tokens (the head is applied to its last only)."""
    return 2.0 * active_body_params(cfg) * t + 2.0 * head_params(cfg) \
        + kda_layers(cfg) * recurrence_flops_per_token_layer(cfg) * t \
        + attention_fwd_flops(cfg, t)
