"""Fixtures of the benchmark's own tests; the toy tree is built by ``toybench.py``."""

import os
import shutil

import pytest

from toybench import REPO, add_toy_cells


@pytest.fixture(scope="session")
def toy_tree(tmp_path_factory):
    tree = str(tmp_path_factory.mktemp("bench"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(tree, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    add_toy_cells(tree)
    return tree
