"""``chip_smoke.py`` off the chip: it refuses the CPU before compiling
anything, its phase functions hold together at toy width on the forced
CPU mesh (Pallas kernels interpreted, asked for explicitly), the compile
cache lands where it is told to, and the peak table matches exactly.

A CPU run of the phases proves structure — shapes, parity, spread,
counts — and nothing about speed; the numbers they return are not
asserted here.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from distkeras_tpu import profiling

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# toy widths: every structural feature of the chip run, seconds on a CPU
RESNET = dict(image=16, classes=4, width=8, stage_sizes=(1, 1),
              rows=128)
LM = dict(layers=1, d_model=32, heads=2, vocab=64, seq=32)


def _run(script, **env):
    return subprocess.run(
        [sys.executable, str(REPO / script)], capture_output=True,
        text=True, timeout=300, cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO), **env})


def test_refuses_cpu_before_compiling(tmp_path):
    """No accelerator: non-zero, the device message, no result line,
    and nothing was compiled — an empty cache directory proves it."""
    for script in ("chip_smoke.py", "bench.py"):
        cache = tmp_path / script
        proc = _run(script, JAX_PLATFORMS="cpu",
                    JAX_COMPILATION_CACHE_DIR=str(cache),
                    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        assert proc.returncode != 0, proc.stdout
        assert "no TPU" in proc.stderr
        assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
        assert not cache.exists() or not any(cache.iterdir())


def test_kernel_facts_interpreted(smoke):
    facts = smoke.kernel_facts(batch=2, seq=64, heads=2, head_dim=16,
                               ref_batch=1, chain=1, interpret=True)
    assert facts["mosaic_calls"] == 0  # interpreted: no Mosaic on a CPU
    assert set(facts["max_err_vs_f32"]) == {"out", "dq", "dk", "dv"}
    assert facts["extract_cost"]["flops"] is not None


def test_decode_kernel_facts_interpreted(smoke):
    facts = smoke.decode_kernel_facts(
        per_head=dict(slots=4, cache_len=256, kvh=8, group=1, width=128),
        shared_leaf=dict(slots=4, cache_len=256, kvh=1, group=4, width=256,
                         v_width=128),
        lengths=(0, 1, 129, 256), interpret=True)
    assert set(facts) == {"per_head", "shared_leaf"}
    assert all(f["max_abs_diff_vs_f32"] < 2e-2 for f in facts.values())


def test_train_resnet_toy(smoke):
    facts = smoke.train_resnet(**RESNET, batch=8, ps_workers=4,
                               ps_batch=4, ps_window=2)
    assert len(facts["single_epoch_loss"]) == 2
    assert len(facts["ps_epoch_loss"]) == 2


def test_train_then_serve_lm_toy(smoke):
    facts, cfg, variables = smoke.train_lm(
        **LM, batch=2, steps=2, parity_layers=1, interpret=True)
    assert facts["mosaic_calls"] == 0
    served = smoke.serve_lm(
        cfg, variables, buckets=(16, 32), align=4, slots=2, kv_pages=16,
        requests=((4, 3), (12, 8)) * 2)
    assert served["requests"] == 4
    # one prefill + one step program per bucket that saw traffic
    assert served["envelope"]["programs"] == served["paged"]["programs"]


def test_pools_in_place_toy(smoke):
    """Nothing is handed over on the CPU, so nothing can fail here but
    the structure: a record a pool, its step and its one prefill."""
    facts = smoke.pools_in_place(
        layers=1, d_model=32, heads=2, vocab=64, seq=32, buckets=(16, 32),
        align=4, slots=2, requests=((4, 3), (12, 8)))
    assert [p["bucket"] for p in facts["pools"]] == [16, 32]
    assert all(set(p["programs"]) == {"step", f"prefill_{t}"}
               for p, t in zip(facts["pools"], (4, 12)))
    # off the chip the decode kernel's rule refuses, and the steps say so
    assert all(p["attended_rows"] == 0 < p["envelope_rows"]
               for p in facts["pools"])


def test_four_devices_toy(smoke, devices):
    facts = smoke.mesh_ps_on_chips(**RESNET, workers=4, batch=4, window=2)
    assert facts["center_device_sets"] == [4]
    # XLA:CPU keeps the opcodes the round asks for
    assert {"all-gather", "reduce-scatter"} <= set(facts["round_collectives"])
    assert facts["ps_round_compiles_total"] == 1
    assert facts["bytes_in_use"] == [None] * 4  # CPU reports none
    facts = smoke.sync_lm_on_chips(**LM, workers=4, batch=2, steps=2)
    assert len(facts["epoch_loss"]) == 2


def test_compile_cache_is_placed_from_outside(tmp_path):
    """With the variable set the helper touches nothing; without it two
    processes agree on one absolute path under the repo."""
    probe = ("import jax; from distkeras_tpu import profiling; "
             "print(profiling.enable_compile_cache()); "
             "print(jax.config.jax_compilation_cache_dir)")

    def ask(**env):
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        out = subprocess.run(
            [sys.executable, "-c", probe], capture_output=True,
            text=True, timeout=120, cwd=str(tmp_path),
            env={**base, "PYTHONPATH": str(REPO), **env})
        assert out.returncode == 0, out.stderr
        return out.stdout.split()

    placed = str(tmp_path / "elsewhere")
    assert ask(JAX_COMPILATION_CACHE_DIR=placed) == [placed, placed]
    first, second = ask(), ask()
    assert first == second == [str(REPO / ".jax_cache")] * 2


def test_peak_lookup_is_exact():
    class Kind:
        def __init__(self, kind):
            self.device_kind = kind

    assert profiling.peak_flops(Kind("TPU v5 lite")) == (197e12, True)
    peak, known = profiling.peak_flops(Kind("TPU v5x"))
    assert peak != peak and known is False  # NaN: not the v5p row
    assert profiling.peak_bandwidth(Kind("TPU v5x"))[1] is False
    assert profiling.peak_flops(jax.devices()[0]) == (1e12, False)
