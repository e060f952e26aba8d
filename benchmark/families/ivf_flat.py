"""IVF-Flat through the served path: ``raft_tpu.neighbors.ivf_flat``
built from the configuration's ``build`` keys, searched with its
``search`` keys."""

from __future__ import annotations


def build(conf: dict, x):
    from raft_tpu import Resources
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.build(Resources(seed=conf["dataset"]["data_seed"]),
                          ivf_flat.IvfFlatIndexParams(**conf["build"]), x)


def describe(index) -> str:
    """The padded list layout, for stderr: slots against stored rows."""
    slots = int(index.indices.shape[0]) * int(index.indices.shape[1])
    return (f"{index.indices.shape[0]} lists x {index.indices.shape[1]} "
            f"slots = {slots} for {index.size} rows")


def search_params(conf: dict):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.IvfFlatSearchParams(**conf["search"])


def work_inputs(conf: dict, index, pool) -> dict:
    """What ``work/ivf_scan.py`` needs: the pool's probed lists and the
    rows each list really stores."""
    from benchmark.work import ivf_scan

    return {"pool_probes": ivf_scan.probes(pool, index.centers,
                                           conf["search"]["n_probes"]),
            "sizes": ivf_scan.list_sizes(index.indices),
            "dim": conf["dataset"]["dim"], "itemsize": 4}
