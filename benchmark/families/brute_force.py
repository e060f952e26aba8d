"""Exact brute-force kNN through the served path:
``raft_tpu.neighbors.brute_force`` with float32 storage."""

from __future__ import annotations


def build(conf: dict, x):
    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force

    return brute_force.build(Resources(seed=conf["dataset"]["data_seed"]), x,
                             **conf["build"])


def search_params(conf: dict):
    return None


def work_inputs(conf: dict, index, pool) -> dict:
    """What ``work/fused_knn.py`` needs."""
    return {"n": conf["dataset"]["n"], "dim": conf["dataset"]["dim"],
            "itemsize": 4}
