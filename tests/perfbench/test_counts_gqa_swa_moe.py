"""The counts of the window + full grouped-query attention, sigmoid-expert
arch (``perfbench/counts/gqa_swa_moe.py``) against counts made by hand at
the configuration's own shapes, and the cut that the configuration file
says: the layers it holds, the uncut model's size, the program's own
parameter tree (its shapes, at the published widths, with
``eval_shape``), and the bytes of the cell's pool."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu.models import build_model
from perfbench import trafficgen
from perfbench.adapters import gqa_swa_moe as adapter
from perfbench.counts import gqa_swa_moe as counts

from toybench import REPO

with open(os.path.join(REPO, "perfbench", "configs",
                       "k-exaone-236b-a23b-l5-e8.json")) as f:
    EXA = json.load(f)
UNCUT = {**EXA, **EXA["published"], "experts_held": [0, 128]}
D = 6144
ATTN = 2 * D * 64 * 128 + 2 * D * 8 * 128 + 2 * 128
EXPERT = 3 * D * 2048


def test_layer_params_by_hand():
    assert counts.attn_params(EXA) == ATTN == 113_246_464
    assert counts.expert_params(EXA) == EXPERT == 37_748_736
    assert counts.dense_ffn_params(EXA) == 3 * D * 18432
    assert counts.router_params(EXA) + counts.shared_params(EXA) \
        == 6145 * 128 + EXPERT


def test_the_cut_is_the_dense_layer_and_one_period_of_four():
    assert counts.layer_kinds(EXA) == [(128, True), (128, False),
                                       (128, False), (0, False),
                                       (128, False)]
    assert (counts.window_layers(EXA), counts.full_layers(EXA),
            counts.expert_layers(EXA)) == (4, 1, 4)
    assert (counts.window_layers(UNCUT), counts.full_layers(UNCUT),
            counts.expert_layers(UNCUT)) == (36, 12, 47)


@pytest.mark.parametrize("cfg,total", [(UNCUT, 236e9), (EXA, 2.505e9)],
                         ids=["uncut", "cut"])
def test_totals_are_the_models_name_and_the_cuts_bytes(cfg, total):
    """~236B-A23B uncut (the multi-token-prediction module left out); the
    cut holds 2.505 B parameters, 5.01 GB in bfloat16."""
    assert counts.total_params(cfg) == pytest.approx(total, rel=0.005)


def test_the_uncut_model_is_active_at_23b():
    assert counts.active_params(UNCUT) == pytest.approx(23e9, rel=0.02)


def test_the_programs_tree_at_the_published_widths_is_the_count():
    model = build_model(adapter.program_model(EXA, 64))
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert n == counts.total_params(EXA) == 2_504_068_864
    assert params["Layer_1_moe"]["w_in"].shape == (8, D, 2 * 2048)
    assert params["Layer_3_attn"]["k"]["kernel"].shape == (D, 8, 128)


def test_the_pool_of_the_cell_is_the_arithmetic():
    """288 slots x 6144: the full layer's keys and values 4 KiB a position
    (7.25 GB), four window layers' rings of 128 (0.60 GB): 12.86 GB with
    the weights."""
    traffic = trafficgen.load("reason-backlog-s288", REPO)
    (env, slots), = ((int(e), n) for e, n in
                     traffic["engine"]["buckets"].items())
    assert (env, slots) == (6144, 288)
    model = build_model(adapter.program_model(EXA, EXA["n_positions"]))
    dec = model.decode_clone()
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))
    cache = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((slots, 1), jnp.int32),
                            mutable=["cache"]), params)[1]["cache"]
    by_kind = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        kind = str(getattr(path[-1], "key", path[-1]))
        by_kind[kind] = by_kind.get(kind, 0) \
            + leaf.size * jnp.dtype(leaf.dtype).itemsize
    assert counts.kv_bytes_per_position_layer(EXA) == 4096
    full = by_kind["cached_key"] + by_kind["cached_value"]
    assert full == 288 * 6144 * 4096 == pytest.approx(7.25e9, rel=1e-3)
    rings = by_kind["window_key"] + by_kind["window_value"]
    assert rings == counts.ring_bytes(EXA, 288) \
        == pytest.approx(0.604e9, rel=1e-3)
    weights = 2 * counts.total_params(EXA)
    assert weights + full + rings == pytest.approx(12.86e9, rel=1e-3)


def test_a_decode_steps_bytes_at_288_rows():
    """The issue's roofline: the weights outside the routed experts 2.36
    GB, the held experts all touched 2.42, the full layer's live cache at
    the mix's slot-time mean of 1924 positions 2.27, the rings 0.60: 9.3
    ms at 819 GB/s, about 30 800 tokens/s."""
    assert counts.non_expert_weight_bytes(EXA) == pytest.approx(2.36e9,
                                                                rel=1e-2)
    touched = 4 * 8 * counts.expert_bytes(EXA)
    assert touched == pytest.approx(2.42e9, rel=1e-2)
    live = 288 * 1924 * counts.kv_bytes_per_token(EXA)
    assert live == pytest.approx(2.27e9, rel=1e-2)
    rings = 288 * counts.ring_bytes_per_row(EXA, 1924)
    step = counts.non_expert_weight_bytes(EXA) + touched + live + rings
    assert step == pytest.approx(7.65e9, rel=1e-2)
    assert 288 / (step / 819e9) == pytest.approx(30800, rel=1e-2)


def test_attention_counts_the_band_and_the_whole_context():
    assert counts.band_pairs(5, 128) == 15
    assert counts.band_pairs(300, 128) == 128 * 129 // 2 + 172 * 128
    per_key = 4 * 64 * 128
    assert counts.swa_prefill_flops(EXA, 300) == \
        4 * per_key * counts.band_pairs(300, 128)
    assert counts.attention_fwd_flops(EXA, 300) == \
        counts.swa_prefill_flops(EXA, 300) + per_key * 300 * 301 // 2
    # a decode token: the full layer over its context, each ring over 128
    assert counts.decode_flops(EXA, 1000) - counts.decode_flops(EXA, 0) \
        == per_key * (1000 + 4 * 128)
    assert counts.held_picks(EXA) == 0.5
