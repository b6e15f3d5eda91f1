"""Hands a latent-attention, sparse-expert configuration and its seeded
weights to the program.

The only file that knows the program's parameter tree
(``distkeras_tpu.models.latent_moe.LatentMoELM``).  It renames leaves and
copies none: the benchmark's layout already is the program's (the routed
experts' gate and up as one ``[E, d, 2 h]`` leaf, a mixer's ``phi``, ``a``
and ``b`` whole).
"""

from distkeras_tpu.models import model_config
# imported for its registration as "latent_moe_lm", and here, where the
# cell's files are loaded, so that a program without the model (any
# commit before PR 28) fails at once and not after making 9.6 GB of
# weights
from distkeras_tpu.models import latent_moe  # noqa: F401

_MIXER = {("norm",): "norm", ("phi",): "phi", ("a",): "a", ("b",): "b"}
_ATTN = {
    ("attn_norm", "scale"): "ln1_g",
    ("attn", "q_down", "kernel"): "wdq", ("attn", "q_norm", "scale"): "qn_g",
    ("attn", "q_up", "kernel"): "wuq", ("attn", "kv_down", "kernel"): "wdkv",
    ("attn", "kv_norm", "scale"): "kvn_g", ("attn", "kv_up"): "wukv",
    ("attn", "out", "kernel"): "wo", ("ffn_norm", "scale"): "ln2_g",
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
    sc = cfg["rope_scaling"]
    if sc["type"] != "yarn" or cfg["scoring_func"] != "sigmoid" \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"]:
        raise SystemExit("the program's block is YaRN rotary, sigmoid "
                         "scores in one group, SiLU, no biases, untied")
    held = cfg.get("experts_held")
    return model_config(
        "latent_moe_lm", (seq_len,), input_dtype="int32",
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], dense_width=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=None if held is None else tuple(held),
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
        rms_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(sc["factor"]), rope_beta_fast=float(sc["beta_fast"]),
        rope_beta_slow=float(sc["beta_slow"]), rope_mscale=float(sc["mscale"]),
        rope_mscale_all_dim=float(sc["mscale_all_dim"]),
        rope_original_max_len=sc["original_max_position_embeddings"],
        max_len=cfg["n_positions"], dtype=cfg["dtype_as_run"], **overrides)


def _layer_paths(i: int, dense: bool):
    """``(program path, benchmark group or None, benchmark leaf)``."""
    pre = f"Layer_{i}_"
    for sub in ("attn", "ffn"):
        for path, name in _MIXER.items():
            yield (f"{pre}{sub}_hc",) + path, f"hc_{sub}", name
    for path, name in {**_ATTN, **(_DENSE if dense else _SPARSE)}.items():
        yield (pre + path[0],) + path[1:], None, name


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
        for path, group, name in _layer_paths(i, "w_gate" in lw):
            _put(params, path, lw[name] if group is None else lw[group][name])
    return {"params": params}
