"""Readings the limits of ``correct`` are set from (a chip tool; the
benchmark's own runs never run it).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 7 8 9 --seconds 3 [--bench <BENCHMARK.json>]

In one process, so set-up compiles once: the program's numbers
(:mod:`benchmark.check`) over a short window of the cell's own traffic
at its own size, for each ``--seeds``; then, for each
``--control-seeds``, the control's: the configuration's reference one
step coarser than its data (its ``control``: bfloat16 for float32 data,
values rounded to even numbers for bytes) put in the program's place,
its answers judged by the same comparison, beside the reference's own
answers judged alike (which must read 0 on bytes), with each phase's
seconds and each chip's peak memory on standard error. ``--bench``
reads the cells of another ``BENCHMARK.json`` (a rehearsal's), whose
files lie beside it. With ``--recall-probes``,
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


def control_reading(cell, seed: int, devs) -> dict:
    """On ``seed`` at the cell's size, over the cell's chips:
    ``{"control": numbers, "reference": numbers}``, the control's
    answers and the reference's own, each judged as the program's."""
    import numpy as np

    from benchmark import check, data

    ds, p = cell.conf["dataset"], int(cell.traffic["pool"])
    ref_mod, limits = cell.reference, cell.conf.get("limits", {})
    phases = run.Phases(devs)
    corpus, pool = data.for_dataset(ds, seed, p, devs)
    x, pool = corpus.array, np.asarray(pool)
    phases.mark("data")
    k = int(ds["k"])
    ref = ref_mod.knn(x, pool, k)
    phases.mark("reference")
    ctl = ref_mod.control(x, pool, k)
    phases.mark("control")
    out = {}
    for name, (d, i) in (("reference", (ref[0].astype(np.float32),
                                        ref[1].astype(np.int32))),
                         ("control", ctl)):
        answers = (np.arange(p), d, i, np.ones(p, bool), 0)
        verdict = check.judge(x, pool, ref, answers, limits,
                              ref_mod.true_distances)
        out[name] = {n: c["value"] for n, c in verdict["checks"].items()}
        phases.mark(f"judge_{name}")
    return out


def recall_curve(cell, seeds, probes, devs) -> dict:
    """Recall@k of the configuration's index over each seed's pool for
    each ``n_probes`` (direct ``search``, the kernels the served path
    runs): ``{seed: {n_probes: recall}}``. One index serves every seed,
    as in the benchmark's runs (the corpus is the configuration's)."""
    import dataclasses

    import numpy as np

    from benchmark import check, data

    ds, p = cell.conf["dataset"], int(cell.traffic["pool"])
    k = int(ds["k"])
    base = cell.family.search_params(cell.conf)
    from raft_tpu.neighbors import ivf_flat

    index, out = None, {}
    for seed in seeds:
        corpus, pool = data.for_dataset(ds, seed, p, devs)
        x = corpus.array
        if index is None:
            index = run.build(cell, corpus, devs)
            print("index", cell.family.describe(index), file=sys.stderr)
        ref = cell.reference.knn(x, pool, k)
        out[seed] = {}
        for n in probes:
            d, i = ivf_flat.search(None, dataclasses.replace(base, n_probes=n),
                                   index, pool, k)
            answers = (np.arange(p), np.asarray(d), np.asarray(i),
                       np.ones(p, bool), 0)
            out[seed][n] = check.judge(x, np.asarray(pool), ref, answers,
                                       {}, cell.reference.true_distances
                                       )["recall"]
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
    ap.add_argument("--bench", default=spec.BENCHMARK_JSON)
    args = ap.parse_args(argv)
    bench = os.path.abspath(args.bench)
    dirs = ((spec.BENCH_DIR,) if bench == spec.BENCHMARK_JSON
            else (os.path.dirname(bench), spec.BENCH_DIR))
    cell = spec.Cell(args.workload, bench, dirs)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    devs = run.require_chips(cell.chips)[:cell.chips]
    prog, ctl = [], []
    for s in args.seeds:
        res = run.measure(cell, s, args.seconds, False, devs)
        r = {n: c["value"] for n, c in res["checks"].items()}
        r.update(seed=s, requests=res["attempted"],
                 metrics={n: m["value"] for n, m in res["metrics"].items()})
        prog.append(r)
        print(json.dumps({"program": r}), flush=True)
    for s in args.control_seeds:
        r = dict(control_reading(cell, s, devs), seed=s)
        ctl.append(r)
        print(json.dumps({"control": r}), flush=True)
    if args.recall_probes:
        recall_curve(cell, args.recall_seeds or list(args.seeds[:1]),
                     args.recall_probes, devs)
    names = ("dist_err", "miss")

    def most(rows, pick):
        return {n: pick(r[n] for r in rows) for n in names} if rows else None

    print(json.dumps({"summary": {
        "workload": args.workload,
        "program_max": most(prog, max),
        "reference_max": most([r["reference"] for r in ctl], max),
        "control_min": most([r["control"] for r in ctl], min)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
