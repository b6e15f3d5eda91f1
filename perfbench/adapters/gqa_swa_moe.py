"""Hands a configuration of sliding-window and full grouped-query
attention over sparse experts, and its seeded weights, to the program.

The only file that knows the program's parameter tree
(``distkeras_tpu.models.window_moe.WindowMoELM``).  It renames leaves and
copies none: the benchmark's layout already is the program's (the heads'
projections ``[d, heads, head_dim]``, the routed experts' gate and up as
one ``[E, d, 2 h]`` leaf).
"""

from distkeras_tpu.models import model_config
# imported for its registration as "window_moe_lm", and here, where the
# cell's files are loaded, so that a program without the model fails at
# once and not after making its weights
from distkeras_tpu.models import window_moe  # noqa: F401

_ATTN = {
    ("attn", "q", "kernel"): "wq", ("attn", "k", "kernel"): "wk",
    ("attn", "v", "kernel"): "wv", ("attn", "q_norm"): "qn_g",
    ("attn", "k_norm"): "kn_g", ("attn", "o", "kernel"): "wo",
    ("attn_norm", "scale"): "ln_attn_g", ("ffn_norm", "scale"): "ln_ffn_g",
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
    n = cfg["num_hidden_layers"]
    kinds = {"sliding_attention", "full_attention"}
    if cfg["scoring_func"] != "sigmoid" or cfg["hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"] \
            or cfg["rope_parameters"]["rope_type"] != "default" \
            or not set(cfg["layer_types"][:n]) <= kinds \
            or any((t == "full_attention") != (w == 0) for t, w in zip(
                cfg["layer_types"][:n], cfg["sliding_windows"][:n])):
        raise SystemExit("the program's block is sliding-window or full "
                         "grouped-query attention (plain rotary on the "
                         "window layers) over SwiGLU experts with sigmoid "
                         "scores, an untied head")
    held = cfg.get("experts_held")
    return model_config(
        "window_moe_lm", (seq_len,), input_dtype="int32",
        vocab_size=cfg["vocab_size"], num_layers=n,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_windows=tuple(int(w) for w in cfg["sliding_windows"][:n]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        dense_width=cfg["intermediate_size"],
        layer_sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"][:n]),
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        experts_held=None if held is None else tuple(held),
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
        table = {**_ATTN, **(_DENSE if "w_gate" in lw else _SPARSE)}
        for path, name in table.items():
            _put(params, (f"Layer_{i}_{path[0]}",) + path[1:], lw[name])
    return {"params": params}
