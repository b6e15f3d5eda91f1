"""Every example script runs end-to-end at tiny shapes (VERDICT r4 #4:
the examples had zero automated coverage — one API rename would break
them silently).

Each script is executed as a real subprocess — exactly how a user runs
it — on a small virtual CPU mesh (``--devices``, the reference's
``local[N]`` analogue), with rows/epochs shrunk to smoke size.  The
scripts' own internal assertions (convergence, decode parity, finite
losses) run too, so this is an integration pass over the whole public
surface, mirroring the reference's notebooks-as-integration-tests
strategy (SURVEY.md §4)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"
SCRIPTS = REPO / "scripts"

# scripts that advertise a --smoke mode run it here at tiny CPU
# shapes — the same no-silent-rot contract as CASES.
SMOKE_SCRIPTS = {
    "chaos_report.py": ["--smoke"],
    "check_protocol.py": ["--smoke"],
    "lint_static.py": ["--smoke"],
    "obs_report.py": ["--smoke"],
    "postmortem.py": ["--smoke"],
}

# script -> tiny-shape args (every script also gets --devices 4).
# Sizes respect each script's internal assertions: convergence checks
# keep enough epochs/rows to actually converge.
CASES = {
    "cifar_convnet_adag.py": ["--rows", "256", "--epochs", "1"],
    "compare_trainers.py": ["--rows", "512", "--epochs", "1"],
    "criteo_widedeep.py": ["--rows", "512", "--epochs", "1"],
    "elastic_training.py": ["--rows", "768", "--epochs", "1"],
    "imagenet_resnet_aeasgd.py": ["--rows", "64", "--epochs", "1",
                                  "--batch-size", "4",
                                  "--image-size", "32",
                                  "--resnet", "18"],
    "imdb_bilstm_dynsgd.py": ["--rows", "256", "--epochs", "1"],
    "keras_import.py": ["--rows", "512", "--epochs", "1"],
    "lm_blockwise_attention.py": ["--rows", "128"],
    "lm_generate.py": ["--rows", "256", "--new-tokens", "8"],
    "lm_seq_parallel.py": ["--rows", "128", "--epochs", "1"],
    "mnist_mlp.py": ["--rows", "1024", "--epochs", "1",
                     "--batch-size", "32", "--trainer", "adag"],
    "out_of_core.py": ["--rows", "1024", "--epochs", "1"],
    "pipeline_lm.py": ["--rows", "128", "--epochs", "1",
                       "--stages", "2", "--layers", "2"],
    "pipeline_moe.py": ["--steps", "5"],
    "streaming_inference.py": ["--rows", "256", "--epochs", "1",
                               "--stream-rows", "50"],
}


def test_every_example_is_covered():
    """A new example must be added to CASES (or this fails loudly)."""
    scripts = {p.name for p in EXAMPLES.glob("*.py")} - {"_common.py"}
    assert scripts == set(CASES), (
        f"examples/ and CASES disagree: "
        f"missing={scripts - set(CASES)} stale={set(CASES) - scripts}")


def test_every_smoke_script_is_covered():
    """A scripts/*.py that grows a --smoke mode must be registered in
    SMOKE_SCRIPTS (or this fails loudly) — same contract as CASES."""
    smoke = {p.name for p in SCRIPTS.glob("*.py")
             if "--smoke" in p.read_text()}
    assert smoke == set(SMOKE_SCRIPTS), (
        f"scripts/ with --smoke and SMOKE_SCRIPTS disagree: "
        f"missing={smoke - set(SMOKE_SCRIPTS)} "
        f"stale={set(SMOKE_SCRIPTS) - smoke}")


@pytest.mark.parametrize("script", sorted(SMOKE_SCRIPTS))
def test_smoke_script_runs(script):
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(SCRIPTS / script), *SMOKE_SCRIPTS[script]],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(REPO))
    assert proc.returncode == 0, (
        f"{script} failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")


@pytest.mark.parametrize("script", sorted(CASES))
def test_example_runs(script):
    env = dict(os.environ, PYTHONPATH=str(REPO))
    # the virtual mesh must be sized before jax initializes in the
    # child; the scripts' own --devices handling does exactly that
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / script), "--devices", "4",
         *CASES[script]],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(REPO))
    assert proc.returncode == 0, (
        f"{script} failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")
