"""Readings the limits of ``correct`` are set from (a chip tool; the
benchmark's own runs never run it).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 7 8 9 --seconds 3

In one process, so set-up compiles once: the program's numbers
(:mod:`benchmark.check`) over a short window of the cell's own traffic
at its own size, for each ``--seeds``; then the control's, for each
``--control-seeds``: the reference computed in bfloat16
(:func:`benchmark.reference.control_knn`) put in the program's place,
its answers judged by the same comparison. With ``--recall-probes``,
IVF recall@k over whole pools for each probe count (``--recall-seeds``):
the curve, and the ``miss`` of a search cut to fewer probes, the fault
``miss`` has to catch. Prints one JSON line per reading and a summary:
the largest program reading (the lower end of a limit) and the smallest
control reading (its upper end).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, spec  # noqa: E402


def control_reading(cell, seed: int) -> dict:
    """The control's numbers on ``seed`` at the cell's size."""
    import numpy as np

    from benchmark import check, data, reference

    ds, p = cell.conf["dataset"], int(cell.traffic["pool"])
    x, pool = data.for_dataset(ds, seed, p)
    k = int(ds["k"])
    ref = reference.exact_knn(x, pool, k)
    d, i = reference.control_knn(x, pool, k)
    answers = (np.arange(p), d, i, np.ones(p, bool), 0)
    out = check.judge(x, np.asarray(pool), ref, answers,
                      cell.conf.get("limits", {}))
    return {name: c["value"] for name, c in out["checks"].items()}


def recall_curve(cell, seeds, probes) -> dict:
    """Recall@k of the configuration's index over each seed's pool for
    each ``n_probes`` (direct ``search``, the kernels the served path
    runs): ``{seed: {n_probes: recall}}``. One index serves every seed,
    as in the benchmark's runs (the corpus is the configuration's)."""
    import dataclasses

    import numpy as np

    from benchmark import check, data, reference

    ds, p = cell.conf["dataset"], int(cell.traffic["pool"])
    k = int(ds["k"])
    base = cell.family.search_params(cell.conf)
    from raft_tpu.neighbors import ivf_flat

    index, out = None, {}
    for seed in seeds:
        x, pool = data.for_dataset(ds, seed, p)
        if index is None:
            index = cell.family.build(cell.conf, x)
            print("index", cell.family.describe(index), file=sys.stderr)
        ref = reference.exact_knn(x, pool, k)
        out[seed] = {}
        for n in probes:
            d, i = ivf_flat.search(None, dataclasses.replace(base, n_probes=n),
                                   index, pool, k)
            answers = (np.arange(p), np.asarray(d), np.asarray(i),
                       np.ones(p, bool), 0)
            out[seed][n] = check.judge(x, np.asarray(pool), ref, answers,
                                       {})["recall"]
        print(json.dumps({"recall_curve": {seed: out[seed]}}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=())
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--recall-probes", type=int, nargs="*", default=())
    ap.add_argument("--recall-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    devs = run.require_chips(cell.chips)
    prog, ctl = [], []
    for s in args.seeds:
        res = run.measure(cell, s, args.seconds, False, devs)
        r = {n: c["value"] for n, c in res["checks"].items()}
        r.update(seed=s, requests=res["attempted"],
                 metrics={n: m["value"] for n, m in res["metrics"].items()})
        prog.append(r)
        print(json.dumps({"program": r}), flush=True)
    for s in args.control_seeds:
        r = dict(control_reading(cell, s), seed=s)
        ctl.append(r)
        print(json.dumps({"control": r}), flush=True)
    if args.recall_probes:
        recall_curve(cell, args.recall_seeds or list(args.seeds[:1]),
                     args.recall_probes)
    names = ("dist_err", "miss")
    print(json.dumps({"summary": {
        "workload": args.workload,
        "program_max": ({n: max(r[n] for r in prog) for n in names}
                        if prog else None),
        "control_min": ({n: min(r[n] for r in ctl) for n in names}
                        if ctl else None)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
