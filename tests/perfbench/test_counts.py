"""The FLOP and byte counts against counts made by hand at the
configurations' own shapes."""

import json
import os

import pytest

from perfbench.counts import gpt2 as counts

from toybench import REPO


def _cfg(name):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


FULL, D4 = _cfg("cerebras-gpt-1.3b"), _cfg("cerebras-gpt-1.3b-d4")
PER_LAYER = 4 * 2048 * 2048 + 2 * 2048 * 8192      # 50 331 648
HEAD = 2048 * 50257                                # 102 926 336


@pytest.mark.parametrize("cfg,layers", [(FULL, 24), (D4, 4)])
def test_matmul_params(cfg, layers):
    assert counts.matmul_params(cfg) == layers * PER_LAYER + HEAD


def test_matmul_params_by_hand():
    assert counts.matmul_params(FULL) == 1_310_885_888
    assert counts.matmul_params(D4) == 304_252_928


def test_train_flops_per_sequence_by_hand():
    t = 2048
    dense = 6 * 304_252_928 * t                       # 3.7386e12
    attn = 3 * 4 * 2 * t * t * 2048                   # 2.0616e11
    assert counts.train_flops_per_sequence(D4, t) == pytest.approx(
        dense + attn)
    assert counts.train_flops_per_sequence(D4, t) == pytest.approx(
        3.94478e12, rel=1e-4)


def test_flash_attention_counts_by_hand():
    need = counts.flash_attention_train(D4, batch=4, t=2048)
    # six products of (2048^2 / 2) x 128 per head, 16 heads, 4 rows, 4 layers
    by_head = 6 * 2 * (2048 * 2048 / 2) * 128
    assert need["flops"] == pytest.approx(by_head * 16 * 4 * 4)
    # twelve passes over one [4, 2048, 2048] bfloat16 tensor a layer
    assert need["bytes"] == pytest.approx(12 * 4 * 2048 * 2048 * 2 * 4)


def test_decode_bytes_by_hand():
    assert counts.weight_bytes(FULL) == 2 * 1_310_885_888   # 2.62 GB
    assert counts.kv_bytes_per_token(FULL) == 196_608
    assert counts.kv_bytes_per_token(D4) == 32_768


def test_decode_and_prefill_flops_by_hand():
    assert counts.decode_flops(FULL, 0) == 2 * 1_310_885_888
    assert counts.decode_flops(FULL, 1000) - counts.decode_flops(FULL, 0) \
        == 24 * 4 * 1000 * 2048
    body = 24 * PER_LAYER
    assert counts.prefill_flops(FULL, 512) == pytest.approx(
        2 * body * 512 + 2 * HEAD + 24 * 2 * 512 * 512 * 2048)


def test_the_cut_configuration_keeps_every_width():
    widths = ("n_embd", "n_head", "n_inner", "vocab_size", "n_positions")
    assert all(FULL[k] == D4[k] for k in widths)
    assert D4["reduced"] == ["n_layer"] and D4["published"]["n_layer"] == 24
