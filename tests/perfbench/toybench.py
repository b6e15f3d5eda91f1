"""A toy benchmark tree for the tests of the benchmark's own code.

The ``toy_tree`` fixture (``conftest.py``) copies ``BENCHMARK.json`` and ``perfbench/`` into a temporary
directory and adds the toy configuration, traffic, limits and metric that
live in ``tests/perfbench/tiny/`` as new files plus entries: the same way
a later PR adds a cell.  Nothing here loads libtpu or asks for a chip.
"""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CPU_PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def add_toy_cells(tree: str) -> None:
    """New files and new entries only; no file of the tree is edited
    except the manifest, which gains entries."""
    for part in ("configs", "traffic", "limits", "metrics"):
        for name in os.listdir(os.path.join(HERE, "tiny", part)):
            dst = os.path.join(tree, "perfbench", part, name)
            assert not os.path.exists(dst), dst
            shutil.copy(os.path.join(HERE, "tiny", part, name), dst)
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"] += [
        {"name": "tiny-gpt", "source": "tests", "reduced": [], "why": "toy",
         "file": "perfbench/configs/tiny-gpt.json"},
        {"name": "tiny-gpt-train", "source": "tests", "reduced": [],
         "why": "toy", "file": "perfbench/configs/tiny-gpt-train.json"}]
    m["workloads"] += [
        {"name": "tiny-steady", "config": "tiny-gpt",
         "traffic": "tiny-poisson", "chips": 1, "why": "toy open loop"},
        {"name": "tiny-train", "config": "tiny-gpt-train",
         "traffic": "tiny-lm", "chips": 1, "why": "toy training job"}]
    for e in m["end_to_end"]:
        if e["name"] in ("ttft_p90_ms", "tpot_p95_ms"):
            e["workloads"].append("tiny-steady")
        if e["name"] == "train_mfu":
            e["workloads"].append("tiny-train")
    m["per_layer"].append(
        {"name": "tiny_tokens_per_step", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "serving engine host side",
         "moves": "tpot_p95_ms", "workloads": ["tiny-steady"]})
    with open(path, "w") as f:
        json.dump(m, f)
