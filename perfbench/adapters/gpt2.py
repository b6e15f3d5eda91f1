"""Hands a GPT-2-shaped configuration and its seeded weights to the program.

This is the only file that knows the program's parameter tree
(``distkeras_tpu.models.transformer.TransformerLM``).  It renames leaves
and makes no copy, except the head's kernel, which the program keeps
apart from the embedding: it is set to the embedding's transpose, the
tied head of the source.
"""

from distkeras_tpu.models import model_config

# program leaf <- benchmark leaf, inside one block
_BLOCK = {
    ("LayerNorm_0", "scale"): "ln1_g", ("LayerNorm_0", "bias"): "ln1_b",
    ("SelfAttention_0", "query", "kernel"): "wq",
    ("SelfAttention_0", "query", "bias"): "bq",
    ("SelfAttention_0", "key", "kernel"): "wk",
    ("SelfAttention_0", "key", "bias"): "bk",
    ("SelfAttention_0", "value", "kernel"): "wv",
    ("SelfAttention_0", "value", "bias"): "bv",
    ("SelfAttention_0", "out", "kernel"): "wo",
    ("SelfAttention_0", "out", "bias"): "bo",
    ("LayerNorm_1", "scale"): "ln2_g", ("LayerNorm_1", "bias"): "ln2_b",
    ("Dense_0", "kernel"): "w1", ("Dense_0", "bias"): "b1",
    ("Dense_1", "kernel"): "w2", ("Dense_1", "bias"): "b2",
}
_GLOBAL = {
    ("Embed_0", "embedding"): "wte", ("pos_embed", "embedding"): "wpe",
    ("LayerNorm_0", "scale"): "lnf_g", ("LayerNorm_0", "bias"): "lnf_b",
    ("lm_head", "kernel"): "head_w", ("lm_head", "bias"): "head_b",
}


def program_model(cfg: dict, seq_len: int, **overrides) -> dict:
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise SystemExit("the program's block fixes the MLP at 4 x d_model")
    return model_config(
        "transformer_lm", (seq_len,), input_dtype="int32",
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"],
        max_len=cfg["n_positions"], dtype=cfg["dtype_as_run"], **overrides)


def _put(tree: dict, path: tuple, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def program_variables(weights: dict) -> dict:
    """``{"params": ...}`` in the program's names, sharing the arrays."""
    g = dict(weights["globals"])
    g.setdefault("head_w", g["wte"].T)
    params: dict = {}
    for path, name in _GLOBAL.items():
        _put(params, path, g[name])
    for i, lw in enumerate(weights["layers"]):
        for path, name in _BLOCK.items():
            _put(params, (f"Block_{i}",) + path, lw[name])
    return {"params": params}


def benchmark_leaf_name(program_path: tuple) -> str:
    """``globals/wte`` or ``layers/3/wq`` for a path in the program's tree,
    so that the program's norms by leaf meet the reference's."""
    path = tuple(p for p in program_path if p != "params")
    if path[0].startswith("Block_"):
        return f"layers/{int(path[0][6:])}/{_BLOCK[path[1:]]}"
    return f"globals/{_GLOBAL[path]}"
