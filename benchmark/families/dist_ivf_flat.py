"""IVF-Flat list-sharded over the cell's chips, built by streaming:
``raft_tpu.distributed.ivf.build_streaming`` over the harness's
:class:`benchmark.data.Corpus`, each chunk labelled on the chip that
holds it, the lists dealt over a one-axis mesh of the cell's devices
and held in the corpus's dtype. Searched through the served path with
the configuration's ``search`` keys.

The configuration's ``stream`` keys give the build's ``train_rows``
(the quantizer's sample) and ``chunk_rows``. A byte corpus needs a
program whose list scan serves byte lists: where the program's engine
resolution says it does not, the adapter refuses before the build
(which would stage lists no chip holds), and it refuses an index that
does not hold the corpus's dtype."""

from __future__ import annotations

import sys

STAGE_PREFIX = "distributed.build."


def build_on(conf: dict, corpus, devices):
    import numpy as np
    from jax.sharding import Mesh

    from raft_tpu import Resources
    from raft_tpu.comms import Comms
    from raft_tpu.distributed import ivf as dist_ivf
    from raft_tpu.neighbors import ivf_flat

    dtype = np.dtype(conf["dataset"]["dtype"])
    if dtype.itemsize == 1:
        require_byte_scan(dtype, conf["dataset"]["dim"])
    comms = Comms(Mesh(np.asarray(list(devices)), ("lists",)), "lists")
    stream = conf["stream"]
    index = dist_ivf.build_streaming(
        Resources(seed=conf["dataset"]["data_seed"]), comms,
        ivf_flat.IvfFlatIndexParams(**conf["build"]), corpus,
        chunk_rows=int(stream["chunk_rows"]),
        train_rows=int(stream["train_rows"]))
    print_stages()
    if index.data.dtype != dtype:
        raise SystemExit(f"dist_ivf_flat: the index holds "
                         f"{index.data.dtype} lists, the corpus {dtype}")
    return index


def require_byte_scan(dtype, dim: int) -> None:
    """Refuse a program whose list scan would not serve ``dtype`` lists
    with its kernel (its ``resolve_scan_engine`` falls back)."""
    import jax

    from raft_tpu.ops import ivf_scan

    lists = jax.ShapeDtypeStruct((1, 32, dim), dtype)
    if ivf_scan.resolve_scan_engine("pallas", data=lists, k=10) != "pallas":
        raise SystemExit(f"dist_ivf_flat: this program's list scan does "
                         f"not serve {dtype} lists")


def print_stages() -> None:
    """Each build stage's seconds on stderr, from the program's
    ``distributed.build.<stage>_seconds`` histograms (nothing where the
    program has none)."""
    from raft_tpu.core import tracing

    for name, h in tracing.histograms(STAGE_PREFIX).items():
        if name.endswith("_seconds") and h["count"]:
            print(f"build stage {name[len(STAGE_PREFIX):-8]} "
                  f"{h['sum']:.3f} s", file=sys.stderr)


def describe(index) -> str:
    """The padded list layout, for stderr: lists x slots over the chips
    against the rows stored."""
    slots = index.n_lists * index.max_list_size
    return (f"{index.n_lists} lists x {index.max_list_size} slots over "
            f"{index.comms.size} chips = {slots} for {index.size} rows "
            f"({slots / index.size:.3f}x), {index.data.dtype}")


def search_params(conf: dict):
    from raft_tpu.neighbors import ivf_flat

    return ivf_flat.IvfFlatSearchParams(**conf["search"])


def work_inputs(conf: dict, index, pool) -> dict:
    """What ``work/mesh_ivf_scan.py`` needs: the pool's probed lists
    (ids in the dealt order the index stores them in) and the rows each
    list really stores."""
    import jax
    import numpy as np

    from benchmark.work import ivf_scan

    centers, sizes = jax.device_get((index.centers, index.list_sizes))
    queries = np.asarray(jax.device_get(pool), np.float32)
    return {"pool_probes": ivf_scan.probes(queries, np.asarray(centers),
                                           conf["search"]["n_probes"]),
            "sizes": np.asarray(sizes, np.int64),
            "dim": conf["dataset"]["dim"], "itemsize": 1}
