"""Fixture family: IVF-Flat list-sharded over the cell's chips
(``raft_tpu.distributed.ivf.build``), built from the corpus and the
devices the harness hands a ``build_on`` adapter."""

from __future__ import annotations


def build_on(conf: dict, corpus, devices):
    import numpy as np
    from jax.sharding import Mesh

    from raft_tpu import Resources
    from raft_tpu.comms import Comms
    from raft_tpu.distributed import ivf as dist_ivf
    from raft_tpu.neighbors import ivf_flat

    comms = Comms(Mesh(np.asarray(list(devices)), ("lists",)), "lists")
    return dist_ivf.build(Resources(seed=conf["dataset"]["data_seed"]), comms,
                          ivf_flat.IvfFlatIndexParams(**conf["build"]),
                          corpus.array)


def describe(index) -> str:
    return (f"{index.n_lists} lists x {index.max_list_size} slots over "
            f"{index.comms.size} chips for {index.size} rows")


def search_params(conf: dict):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.IvfFlatSearchParams(**conf["search"])
