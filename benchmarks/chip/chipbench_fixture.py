"""A throwaway copy of the benchmark at a size the CPU tests can run.

``tiny_root(tmp)`` copies ``benchmarks/chip`` and ``BENCHMARK.json`` into
``tmp``, links the program's ``src``, and shrinks each configuration and
serving mix in the copy only; the harness then runs there with
``require_tpu=False``.
"""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_CONFIG = {"n_points": 4000, "join_cut": 2}
TINY_EPS = {"syn2d": 1.5}
TINY_TRAFFIC = {"rate_rps": 20, "max_batch": 128, "check_queries": 300}


def tiny_root(tmp: str, *, shrink: bool = True) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(HERE, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("runs", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    if shrink:
        chip = os.path.join(root, "benchmarks", "chip")
        for name, eps in TINY_EPS.items():
            edit_json(os.path.join(chip, "configs", f"{name}.json"),
                      {**TINY_CONFIG, "eps": eps})
        for f in os.listdir(os.path.join(chip, "traffic")):
            path = os.path.join(chip, "traffic", f)
            with open(path) as fh:
                if json.load(fh)["driver"] == "serve":
                    edit_json(path, TINY_TRAFFIC)
    return root


def edit_json(path: str, changes: dict) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
