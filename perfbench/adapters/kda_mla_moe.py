"""Hands a configuration of KDA linear-attention and latent-attention
layers over sparse experts, and its seeded weights, to the program.

The only file that knows the program's parameter tree
(``distkeras_tpu.models.hybrid_moe.HybridMoELM``).  It renames leaves and
copies none: the benchmark's layout already is the program's (``q``,
``k`` and ``v`` as one ``[d, 3 H d_k]`` leaf, the routed experts' gate and
up as one ``[E, d, 2 h]`` leaf).
"""

from distkeras_tpu.models import model_config
# imported for its registration as "hybrid_moe_lm", and here, where the
# cell's files are loaded, so that a program without the model fails at
# once and not after making its weights
from distkeras_tpu.models import hybrid_moe  # noqa: F401

_NORMS = {("attn_norm", "scale"): "ln1_g", ("ffn_norm", "scale"): "ln2_g"}
_KDA = {
    ("attn", "qkv", "kernel"): "w_qkv", ("attn", "conv"): "conv_w",
    ("attn", "f", "kernel"): "w_f", ("attn", "A_log"): "a_log",
    ("attn", "dt_bias"): "dt_bias", ("attn", "b", "kernel"): "w_b",
    ("attn", "g", "kernel"): "w_g", ("attn", "o_norm"): "on_g",
    ("attn", "o", "kernel"): "w_o",
}
_MLA = {
    ("attn", "q", "kernel"): "wq", ("attn", "kv_down", "kernel"): "wdkv",
    ("attn", "kv_norm", "scale"): "kvn_g", ("attn", "kv_up"): "wukv",
    ("attn", "gate", "kernel"): "w_hg", ("attn", "out", "kernel"): "wo",
}
_DENSE = {("mlp", "gate", "kernel"): "w_gate", ("mlp", "up", "kernel"): "w_up",
          ("mlp", "down", "kernel"): "w_down"}
_SPARSE = {
    ("moe", "router"): "router", ("moe", "bias"): "e_bias",
    ("moe", "w_in"): "we_in", ("moe", "w_out"): "we_down",
    ("moe", "shared", "gate", "kernel"): "ws_gate",
    ("moe", "shared", "up", "kernel"): "ws_up",
    ("moe", "shared", "down", "kernel"): "ws_down",
}
_GLOBAL = {("Embed_0", "embedding"): "wte", ("final_norm", "scale"): "lnf_g",
           ("lm_head", "kernel"): "head_w"}


def program_model(cfg: dict, seq_len: int, **overrides) -> dict:
    if cfg["score_function"] != "sigmoid" or cfg["q_lora_rank"] is not None \
            or cfg["num_kv_heads_for_linear_attn"] != 0 \
            or cfg["group_norm_size"] != 1 or not cfg["linear_silu"] \
            or not cfg["no_kda_lora"] or not cfg["kda_safe_gate"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or cfg["linear_state_dtype"] != "float32" \
            or cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
        raise SystemExit("the program's block is KDA (full-rank gate, one "
                         "norm, every head its own, float32 state) beside "
                         "MLA with no query low rank and a head-wise gate, "
                         "sigmoid scores")
    held = cfg.get("experts_held")
    n = cfg["num_hidden_layers"]
    return model_config(
        "hybrid_moe_lm", (seq_len,), input_dtype="int32",
        vocab_size=cfg["vocab_size"], num_layers=n,
        d_model=cfg["hidden_size"], layer_group_size=cfg["layer_group_size"],
        kda_heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        conv_width=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        num_heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        dense_width=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        experts_held=None if held is None else tuple(held),
        expert_limits=tuple(cfg["expert_swiglu_limit_list"][:n]),
        shared_limits=tuple(cfg["share_expert_swiglu_limit_list"][:n]),
        rms_eps=cfg["rms_norm_eps"], max_len=cfg["n_positions"],
        dtype=cfg["dtype_as_run"], **overrides)


def _put(tree: dict, path: tuple, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def program_variables(weights: dict) -> dict:
    """``{"params": ...}`` in the program's names, sharing the arrays."""
    params: dict = {}
    for path, name in _GLOBAL.items():
        _put(params, path, weights["globals"][name])
    for i, lw in enumerate(weights["layers"]):
        table = {**_NORMS, **(_MLA if "wq" in lw else _KDA),
                 **(_DENSE if "w_gate" in lw else _SPARSE)}
        for path, name in table.items():
            _put(params, (f"Layer_{i}_{path[0]}",) + path[1:], lw[name])
    return {"params": params}
