"""Regenerate docs/api.md — the full API reference — from the live
package: per module, every public function's signature + summary line
and every public class with its fields/methods (the role of the
reference's generated doc site, ``docs/source/``).

Run:  JAX_PLATFORMS=cpu python docs/gen_api.py
"""

import dataclasses
import importlib
import inspect
import pathlib
import re

MODULES = [
    "raft_tpu.core.resources", "raft_tpu.core.chips",
    "raft_tpu.core.executor",
    "raft_tpu.core.bitset", "raft_tpu.core.logger",
    "raft_tpu.core.tracing", "raft_tpu.core.interruptible",
    "raft_tpu.core.serialize", "raft_tpu.core.operators",
    "raft_tpu.core.validation",
    "raft_tpu.analysis", "raft_tpu.analysis.core",
    "raft_tpu.analysis.astutil", "raft_tpu.analysis.report",
    "raft_tpu.distance", "raft_tpu.distance.types",
    "raft_tpu.distance.fused_l2_nn", "raft_tpu.distance.masked_nn",
    "raft_tpu.distance.kernels",
    "raft_tpu.linalg", "raft_tpu.matrix", "raft_tpu.matrix.select_k",
    "raft_tpu.ops",
    "raft_tpu.random", "raft_tpu.stats", "raft_tpu.label",
    "raft_tpu.sparse.types", "raft_tpu.sparse.convert",
    "raft_tpu.sparse.linalg",
    "raft_tpu.sparse.distance", "raft_tpu.sparse.neighbors",
    "raft_tpu.sparse.ops", "raft_tpu.sparse.solver",
    "raft_tpu.cluster.kmeans", "raft_tpu.cluster.kmeans_balanced",
    "raft_tpu.cluster.single_linkage", "raft_tpu.spectral", "raft_tpu.solver",
    "raft_tpu.neighbors.ann_types",
    "raft_tpu.neighbors.brute_force", "raft_tpu.neighbors.ivf_flat",
    "raft_tpu.neighbors.ivf_pq", "raft_tpu.neighbors.ivf_bq",
    "raft_tpu.neighbors.cagra", "raft_tpu.neighbors.hnsw",
    "raft_tpu.neighbors.nn_descent", "raft_tpu.neighbors.cluster_join",
    "raft_tpu.neighbors.refine",
    "raft_tpu.neighbors.ball_cover", "raft_tpu.neighbors.epsilon_neighborhood",
    "raft_tpu.neighbors.quantized", "raft_tpu.neighbors.filters",
    "raft_tpu.neighbors.ivf_helpers", "raft_tpu.neighbors.tiered",
    "raft_tpu.ops.tier_scan",
    "raft_tpu.spatial.knn",
    "raft_tpu.serving", "raft_tpu.serving.request",
    "raft_tpu.serving.batcher", "raft_tpu.serving.admission",
    "raft_tpu.serving.metrics", "raft_tpu.serving.exporter",
    "raft_tpu.serving.harness", "raft_tpu.serving.gauge",
    "raft_tpu.serving.flight", "raft_tpu.serving.continuous",
    "raft_tpu.serving.federation", "raft_tpu.serving.placement",
    "raft_tpu.serving.prefetch",
    "raft_tpu.fleet", "raft_tpu.fleet.table",
    "raft_tpu.fleet.planner", "raft_tpu.fleet.router",
    "raft_tpu.fleet.harness",
    "raft_tpu.core.profiling",
    "raft_tpu.core.xplane", "raft_tpu.core.memwatch",
    "raft_tpu.comms", "raft_tpu.comms.bootstrap",
    "raft_tpu.distributed.ivf", "raft_tpu.distributed.knn",
    "raft_tpu.distributed.kmeans", "raft_tpu.distributed.sharded_ann",
    "raft_tpu.distributed.checkpoint", "raft_tpu.distributed.bq",
    "raft_tpu.io",
    "raft_tpu.bench", "raft_tpu.bench.datasets", "raft_tpu.bench.runner",
    "raft_tpu.bench.prims", "raft_tpu.bench.hnsw_cpu",
    "raft_tpu.bench.ivf_flat_cpu",
    "raft_tpu.utils",
]


def first_para(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    para = doc.split("\n\n", 1)[0].strip()
    return " ".join(para.split())


def sig_of(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # callable defaults repr with a process-specific address
    # ("<function sum at 0x7f...>"); strip it so regeneration is
    # byte-stable across runs/machines
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def public_symbols(m, name):
    pub = []
    names = getattr(m, "__all__", None) or sorted(vars(m))
    for s in names:
        if s.startswith("_"):
            continue
        obj = getattr(m, s, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if inspect.isfunction(obj) or inspect.isclass(obj):
            defmod = getattr(obj, "__module__", "")
            # list a symbol where it is DEFINED (or explicitly
            # re-exported via __all__) — cross-module imports like
            # serialize helpers or private packing utilities are
            # not part of that module's public surface
            explicit = s in (getattr(m, "__all__", None) or ())
            if defmod == name or (explicit
                                  and defmod.startswith("raft_tpu")):
                pub.append((s, obj))
    return pub


def render_class(s, obj, lines):
    lines.append(f"### class `{s}`")
    lines.append("")
    doc = first_para(obj)
    if doc:
        lines.append(doc)
        lines.append("")
    if dataclasses.is_dataclass(obj):
        rows = []
        for f in dataclasses.fields(obj):
            default = ""
            if f.default is not dataclasses.MISSING:
                default = f" = {f.default!r}"
            elif f.default_factory is not dataclasses.MISSING:  # type: ignore
                default = " = <factory>"
            rows.append(f"- `{f.name}{default}`")
        if rows:
            lines.append("Fields:")
            lines.extend(rows)
            lines.append("")
    # public methods/properties defined on the class itself (enums skip
    # this: their members are values, not callables). Descriptor check
    # must come BEFORE callable(): classmethod/property objects are not
    # callable in CPython
    for mn, mv in sorted(vars(obj).items()):
        if mn.startswith("_"):
            continue
        if isinstance(mv, property):
            lines.append(f"- **`.{mn}`** (property) — "
                         f"{first_para(mv.fget) if mv.fget else ''}")
            continue
        if isinstance(mv, (staticmethod, classmethod)):
            mv = mv.__func__
        if not inspect.isfunction(mv):
            continue
        lines.append(f"- **`.{mn}{sig_of(mv)}`** — {first_para(mv)}")
    if lines[-1] != "":
        lines.append("")


def main():
    lines = [
        "# raft_tpu API reference", "",
        "Generated from the live package (`python docs/gen_api.py`); "
        "every public function with its signature and summary, every "
        "public class with its fields and methods. Module docstrings "
        "cite the reference-RAFT files they re-design "
        "(see PARITY.md for the mapping).", "",
        "Modules:", "",
    ]
    toc = []
    body = []
    for name in MODULES:
        m = importlib.import_module(name)
        pub = public_symbols(m, name)
        if not pub:
            continue
        anchor = name.replace(".", "")
        toc.append(f"- [`{name}`](#{anchor})")
        body.append(f"## `{name}`")
        body.append("")
        mdoc = first_para(m)
        if mdoc:
            body.append(mdoc)
            body.append("")
        for s, obj in pub:
            if inspect.isclass(obj):
                render_class(s, obj, body)
            else:
                body.append(f"### `{s}{sig_of(obj)}`")
                body.append("")
                doc = first_para(obj)
                if doc:
                    body.append(doc)
                    body.append("")
    out = pathlib.Path(__file__).parent / "api.md"
    out.write_text("\n".join(lines + toc + [""] + body) + "\n")
    n_funcs = sum(1 for line in body if line.startswith("### `"))
    n_classes = sum(1 for line in body if line.startswith("### class"))
    print(f"wrote {out} ({len(toc)} modules, {n_funcs} functions, "
          f"{n_classes} classes)")


if __name__ == "__main__":
    main()
