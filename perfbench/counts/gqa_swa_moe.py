"""Operations and bytes that the decoder of sliding-window and full
grouped-query attention over sparse experts needs, from shapes.  As
``counts/mla_moe_hc.py``: what the algorithm needs, whatever implements
it (no padding, no copies, no dead rows, causal attention at half the
square, a token through those of its chosen experts that are held here).

A position a layer caches ``KVH`` keys and values of ``head_dim``
(``kv_bytes_per_position_layer``).  A full layer attends a token over its
whole context; a window layer over ``min(context, W)`` positions, in a
decode step read from its ring and in a prefill as the band of the
causal square (``band_pairs``).
"""


def _d(cfg: dict) -> int:
    return cfg["hidden_size"]


def attn_params(cfg: dict) -> int:
    """One layer's attention: ``q`` and ``o`` at ``d x H D``, ``k`` and
    ``v`` at ``d x KVH D``, the two heads' norms."""
    d, h, kvh, hd = _d(cfg), cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    return 2 * d * h * hd + 2 * d * kvh * hd + 2 * hd


def expert_params(cfg: dict) -> int:
    return 3 * _d(cfg) * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg: dict) -> int:
    return (_d(cfg) + 1) * cfg["num_experts"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * _d(cfg) * cfg["intermediate_size"]


def experts_held(cfg: dict) -> int:
    return cfg.get("experts_held", (0, cfg["num_experts"]))[1]


def layer_kinds(cfg: dict) -> list:
    """``(window, dense)`` of each layer (window 0: a full layer)."""
    return [(int(cfg["sliding_windows"][i]),
             cfg["mlp_layer_types"][i] == "dense")
            for i in range(cfg["num_hidden_layers"])]


def window_layers(cfg: dict) -> int:
    return sum(bool(w) for w, _ in layer_kinds(cfg))


def full_layers(cfg: dict) -> int:
    return sum(not w for w, _ in layer_kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in layer_kinds(cfg))


def window(cfg: dict) -> int:
    return max(w for w, _ in layer_kinds(cfg))


def layer_params(cfg: dict, dense: bool) -> int:
    ffn = dense_ffn_params(cfg) if dense else router_params(cfg) \
        + experts_held(cfg) * expert_params(cfg) + shared_params(cfg)
    return attn_params(cfg) + ffn + 2 * _d(cfg)


def vocabulary_params(cfg: dict) -> int:
    """The embedding, the untied head and the final norm."""
    return 2 * _d(cfg) * cfg["vocab_size"] + _d(cfg)


def total_params(cfg: dict) -> int:
    return sum(layer_params(cfg, dense) for _, dense in layer_kinds(cfg)) \
        + vocabulary_params(cfg)


def head_params(cfg: dict) -> int:
    return _d(cfg) * cfg["vocab_size"]


def held_picks(cfg: dict) -> float:
    """A token's chosen experts that are held here, in the mean (routing
    spreads a token's choices over all experts)."""
    return cfg["num_experts_per_tok"] * experts_held(cfg) / cfg["num_experts"]


def active_body_params(cfg: dict) -> float:
    """Parameters of the layers that take part in a product for a token."""
    total = 0.0
    for _, dense in layer_kinds(cfg):
        total += attn_params(cfg)
        total += dense_ffn_params(cfg) if dense else router_params(cfg) \
            + held_picks(cfg) * expert_params(cfg) + shared_params(cfg)
    return total


def active_params(cfg: dict) -> float:
    return active_body_params(cfg) + head_params(cfg)


def kv_bytes_per_position_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """One cached position of one layer: keys and values of every cached
    head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    """One context token in the full layers' cache."""
    return float(full_layers(cfg)
                 * kv_bytes_per_position_layer(cfg, bytes_per_value))


def ring_bytes_per_row(cfg: dict, context: int,
                       bytes_per_value: int = 2) -> float:
    """What a decode step reads of one live row's rings: ``min(context,
    W)`` positions in every window layer."""
    return float(window_layers(cfg) * min(context, window(cfg))
                 * kv_bytes_per_position_layer(cfg, bytes_per_value))


def ring_bytes(cfg: dict, rows: int, bytes_per_value: int = 2) -> float:
    """The rings of ``rows`` slots, every window layer: ``W`` positions
    each, whatever the context."""
    return float(rows * window_layers(cfg) * window(cfg)
                 * kv_bytes_per_position_layer(cfg, bytes_per_value))


def attention_flops_per_key_layer(cfg: dict) -> int:
    """One query token against one key in one layer: ``q . k`` and ``p v``
    for every query head."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def band_pairs(t: int, w: int) -> int:
    """(query, key) pairs of a causal band of ``w`` keys over ``t``
    tokens: ``sum_i min(i + 1, w)``."""
    if t <= w:
        return t * (t + 1) // 2
    return w * (w + 1) // 2 + (t - w) * w


def swa_prefill_flops(cfg: dict, t: int) -> float:
    """The window layers' attention over a prompt of ``t`` tokens."""
    return float(attention_flops_per_key_layer(cfg) * sum(
        band_pairs(t, w) for w, _ in layer_kinds(cfg) if w))


def attention_fwd_flops(cfg: dict, t: int) -> float:
    """Every layer's attention over a prompt of ``t`` tokens."""
    return swa_prefill_flops(cfg, t) + float(
        full_layers(cfg) * attention_flops_per_key_layer(cfg)
        * band_pairs(t, t))


def decode_flops(cfg: dict, context: int) -> float:
    """One new token against ``context`` cached ones."""
    keys = sum(min(context, w) if w else context
               for w, _ in layer_kinds(cfg))
    return 2.0 * active_params(cfg) \
        + float(attention_flops_per_key_layer(cfg) * keys)


def prefill_flops(cfg: dict, t: int) -> float:
    """A prompt of ``t`` tokens (the head is applied to its last only)."""
    return 2.0 * active_body_params(cfg) * t + 2.0 * head_params(cfg) \
        + attention_fwd_flops(cfg, t)


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
