"""A later PR can add a configuration, a traffic mix, a cell and a
per-layer metric by adding files and entries only."""

import hashlib
import json
import os
import subprocess
import sys

from toybench import HERE, REPO


def digest(root):
    out = {}
    for folder, _, files in os.walk(root):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_the_toy_cells_are_new_files_plus_entries(toy_tree):
    before = digest(os.path.join(REPO, "perfbench"))
    after = digest(os.path.join(toy_tree, "perfbench"))
    # no file that the benchmark had is edited or gone
    assert all(after.get(path) == sha for path, sha in before.items())
    added = sorted(set(after) - set(before))
    assert added == sorted(
        os.path.join(part, name)
        for part in ("configs", "traffic", "limits", "metrics")
        for name in os.listdir(os.path.join(HERE, "tiny", part)))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(toy_tree, "BENCHMARK.json")) as f:
        new = json.load(f)
    # entries are appended; the cells and metrics that were there stay
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) > len(old[key])
    assert new["run_seconds"] == old["run_seconds"]
    assert [m["bound"] for m in new["end_to_end"]] == \
        [m["bound"] for m in old["end_to_end"]]


def test_the_added_cell_runs_from_the_copied_tree_alone(toy_tree):
    """The harness finds the new configuration, mix, limits and reader by
    the names in the manifest: run from the copy, in a process of its own
    whose ``perfbench`` is the copy's."""
    code = (
        "import sys, json\n"
        f"sys.path[:0] = [{toy_tree!r}, {REPO!r}]\n"
        "import perfbench.run as r\n"
        f"assert r.REPO == {toy_tree!r}, r.REPO\n"
        "import importlib\n"
        "assert importlib.import_module("
        "'perfbench.metrics.tiny_tokens_per_step').read\n"
        "line = r.run_cell('tiny-steady', 2**31 + 3, 0.5, 0, "
        "{'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9})\n"
        "print(json.dumps(line))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def _command(tree, pythonpath):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath,
               JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jc"))
    return subprocess.run(
        [sys.executable, os.path.join(tree, "perfbench", "run.py"),
         "--workload", "tiny-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600, cwd=tree)


def test_the_command_gives_no_result_without_a_chip(toy_tree):
    """``perfbench/run.py`` itself looks for the chip first: here, where
    JAX is held to the CPU, it exits non-zero and prints no result."""
    proc = _command(toy_tree, REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_the_command_gives_no_result_without_the_program(toy_tree):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` there is no system to test: non-zero, no result."""
    proc = _command(toy_tree, "")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "distkeras_tpu" in proc.stderr
