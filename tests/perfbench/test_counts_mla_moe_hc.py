"""The FLOP and byte counts of the latent-attention, sparse-expert arch
(``perfbench/counts/mla_moe_hc.py``) against counts made by hand at the
configuration's own shapes, and the cut written into the configuration
file."""

import json
import os

import pytest

from perfbench.counts import mla_moe_hc as moe_counts

from toybench import REPO


def _cfg(name):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)

XING = _cfg("xing4.0-29b-a4b-l6")
XING_UNCUT = {**XING, **XING["published"]}
ATTN = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
    + 32 * 128 * 3584                                  # 28 409 856
EXPERT = 3 * 3584 * 1024                               # 11 010 048
MIXING = 2 * (4 * 3584 * 25 + 3 + 24)                  # 716 854
NORMS = 2 * 3584 + 768 + 512


def test_xing_layer_params_by_hand():
    assert moe_counts.attention_params(XING) == ATTN == 28_409_856
    assert moe_counts.expert_params(XING) == EXPERT == 11_010_048
    assert moe_counts.mixing_params(XING) == MIXING == 716_854
    assert moe_counts.dense_layer_params(XING) == \
        ATTN + NORMS + MIXING + 3 * 3584 * 9216
    assert moe_counts.expert_layer_params(XING) == \
        ATTN + NORMS + MIXING + 3585 * 64 + 65 * EXPERT == 745_017_718


@pytest.mark.parametrize("cfg,total,active", [
    (XING_UNCUT, 29.51e9, 3.91e9), (XING, 4.793e9, 1.017e9)],
    ids=["uncut", "cut"])
def test_xing_totals_are_the_models_name_and_the_cuts_bytes(
        cfg, total, active):
    """29B-A4B: 2 dense layers, 38 expert layers, embedding and head; the
    cut holds 1 + 5 of them with every expert and the whole vocabulary.
    The active count here includes the stream mixers' products (0.72 M a
    layer), which the issue's 3.91 B leaves out of the expert layers."""
    assert moe_counts.total_params(cfg) == pytest.approx(total, rel=1e-3)
    assert moe_counts.active_params(cfg) == pytest.approx(active, rel=7e-3)


def test_xing_bytes_by_hand():
    assert moe_counts.total_params(XING) == 4_792_841_860
    assert moe_counts.latent_bytes_per_token_layer(XING) == 1152
    assert moe_counts.kv_bytes_per_token(XING) == 6 * 1152
    # a step reads everything but the embedding table: 8.65 GB, of which
    # 7.05 GB the routed experts
    assert moe_counts.weight_bytes(XING) == pytest.approx(8.646e9, rel=1e-3)
    assert moe_counts.weight_bytes(XING) \
        - moe_counts.non_expert_weight_bytes(XING) == 5 * 64 * EXPERT * 2
    assert moe_counts.expert_bytes(XING) == 2 * EXPERT


def test_xing_flops_by_hand():
    per_context = 2 * 32 * 576 + 2 * 32 * 512
    assert moe_counts.absorbed_flops_per_context_token_layer(XING) \
        == per_context == 69_632
    active = moe_counts.active_params(XING)
    assert moe_counts.decode_flops(XING, 0) == 2 * active
    assert moe_counts.decode_flops(XING, 1000) - 2 * active \
        == 6 * per_context * 1000
    body = active - 3584 * 131072
    assert moe_counts.prefill_flops(XING, 512) == pytest.approx(
        2 * body * 512 + 2 * 3584 * 131072
        + 6 * 512 * 512 * 32 * (192 + 128))
    assert moe_counts.expert_flops_per_assignment(XING) == 6 * 3584 * 1024


def test_the_xing_cut_keeps_every_width_and_says_what_it_stands_for():
    assert XING["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert XING["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2}
    published = {
        "hidden_size": 3584, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "num_attention_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "vocab_size": 131072, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "routed_scaling_factor": 2, "max_position_embeddings": 262144}
    assert {k: XING[k] for k in published} == published
    assert XING["rope_scaling"]["factor"] == 64
    assert "seven pipeline stages" in XING["deployment"]
    assert any("multi-token-prediction" in d for d in XING["departures"])
    assert len(XING["assumed"]) >= 5
