"""The counts of the KDA + latent-attention, grouped-expert arch
(``perfbench/counts/kda_mla_moe.py``) against counts made by hand at the
configuration's own shapes, and the cut that the configuration file says:
the layers it holds, the program's own parameter tree (its shapes, at the
published widths, with ``eval_shape``), and the bytes of the cell's pool."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu.models import build_model
from perfbench import trafficgen
from perfbench.adapters import kda_mla_moe as adapter
from perfbench.counts import kda_mla_moe as counts

from toybench import REPO

with open(os.path.join(REPO, "perfbench", "configs",
                       "ling-3.0-flash-vl-l7-e64.json")) as f:
    LING = json.load(f)
UNCUT = {**LING, **LING["published"], "experts_held": [0, 512]}
D, HD = 2560, 32 * 128
KDA = 6 * D * HD + D * 32 + 4 * 3 * HD + 32 + 2 * HD
MLA = D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + D * 32 + 32 * 128 * D
EXPERT = 3 * D * 768


def test_layer_params_by_hand():
    assert counts.kda_params(LING) == KDA == 63_053_856
    assert counts.mla_params(LING) == MLA == 31_965_696
    assert counts.expert_params(LING) == EXPERT == 5_898_240
    assert counts.router_params(LING) + counts.shared_params(LING) \
        == 2561 * 512 + EXPERT


def test_the_cut_is_one_dense_layer_and_one_period_of_six():
    kinds = counts.layer_kinds(LING)
    assert kinds == [(False, True)] + [(False, False)] * 4 \
        + [(True, False), (False, False)]
    assert (counts.kda_layers(LING), counts.latent_layers(LING),
            counts.expert_layers(LING)) == (6, 1, 6)
    assert (counts.kda_layers(UNCUT), counts.latent_layers(UNCUT)) \
        == (35, 7)


@pytest.mark.parametrize("cfg,total", [(UNCUT, 125e9), (LING, 2.866e9)],
                         ids=["uncut", "cut"])
def test_totals_are_the_models_name_and_the_cuts_bytes(cfg, total):
    """~125B-A5.5B uncut (the vision tower left out); the stage holds 2.866
    B parameters, 5.73 GB in bfloat16."""
    assert counts.total_params(cfg) == pytest.approx(total, rel=0.02)


def test_the_programs_tree_at_the_published_widths_is_the_count():
    model = build_model(adapter.program_model(LING, 64))
    params = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert n == counts.total_params(LING) == 2_866_291_904
    experts = params["Layer_1_moe"]["w_in"]
    assert experts.shape == (64, D, 2 * 768)


def test_the_pool_of_the_cell_is_the_arithmetic():
    """256 slots x 6144: the KDA state 2 MiB a layer a slot (3.22 GB), the
    convolution's tail (0.11 GB) and the latent padded to 640 (2.01 GB)."""
    traffic = trafficgen.load("reason-backlog-s256", REPO)
    (env, slots), = ((int(e), n) for e, n in
                     traffic["engine"]["buckets"].items())
    assert (env, slots) == (6144, 256)
    model = build_model(adapter.program_model(LING, LING["n_positions"]))
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
    assert counts.state_bytes_per_row_layer(LING) == 2 * 2**20
    assert by_kind["recurrent_state"] == 256 * 6 * 2 * 2**20 \
        == pytest.approx(3.22e9, rel=1e-3)
    assert by_kind["recurrent_conv"] == \
        256 * 6 * counts.conv_bytes_per_row_layer(LING) \
        == pytest.approx(0.113e9, rel=1e-2)
    assert by_kind["cached_latent"] == 256 * 6144 * 1280 \
        == pytest.approx(2.01e9, rel=1e-2)


def test_a_decode_steps_bytes_at_256_rows():
    """The issue's table: 2048 picks a layer over 512 experts touch 63 of
    the 64 held here; the other weights 1.10 GB; the live rows' state read
    and written, 6.44 GB; the tails 0.23 GB; a mean context of 1.8k."""
    touched = 6 * 63 * counts.expert_bytes(LING)
    assert touched == pytest.approx(4.45e9, rel=1e-2)
    assert counts.non_expert_weight_bytes(LING) == pytest.approx(1.10e9,
                                                                 rel=1e-2)
    state = 256 * counts.kda_step_bytes_per_row(LING)
    assert state == pytest.approx(6.44e9 + 0.23e9, rel=1e-2)
    latent = 256 * 1800 * counts.kv_bytes_per_token(LING)
    step = touched + counts.non_expert_weight_bytes(LING) + state + latent
    assert step == pytest.approx(12.8e9, rel=2e-2)
    assert 256 / (step / 819e9) == pytest.approx(16400, rel=2e-2)


def test_decode_flops_count_a_tokens_held_picks():
    assert counts.held_picks(LING) == 1.0
    per_context = counts.decode_flops(LING, 1) - counts.decode_flops(LING, 0)
    assert per_context == 2 * 32 * 576 + 2 * 32 * 512
    assert counts.prefill_flops(LING, 1000) > 1000 * 2 * counts.\
        active_body_params(LING)
