"""Finds what ``BENCHMARK.json`` names, by name, in files of their own.

- a configuration: ``configs/<config>.json`` (sizes, family,
  reference, limits; ``dataset.dtype`` is ``float32``, ``uint8`` or
  ``int8``, and a byte dtype adds ``byte_scale`` and ``byte_offset``,
  see :mod:`benchmark.data`);
- a traffic mix: ``traffic/<traffic>.json`` (read by
  :mod:`benchmark.traffic`);
- an index family's adapter: ``families/<family>.py`` (the
  configuration's ``family``);
- the plain reference: ``references/<reference>.py`` (the
  configuration's ``reference``);
- a kernel's work function: ``work/<kernel>.py``;
- a per-layer metric's reader: ``metrics/<metric>.py``.

Each is looked up in ``dirs`` in order (the benchmark's own directory
by default), so a later PR adds a cell, a configuration or a metric by
adding files and entries, and edits no file that is there.

What the harness calls on them:

- a family adapter: ``build_on(conf, corpus, devices)`` if it has one,
  given the :class:`benchmark.data.Corpus` (``array``, ``n_rows``,
  ``dim``, ``dtype``, ``iter_chunks(chunk_rows)`` yielding
  ``(first_row, rows)`` on the chip that holds them) and the cell's
  ``chips`` devices, so a mesh adapter can build its ``Comms`` over
  them; otherwise ``build(conf, x)`` with the corpus's array.
  Then ``search_params(conf)``; ``work_inputs(conf, index, pool)``
  where the configuration names a ``kernel``; ``describe(index)``
  optionally, for stderr.
- a reference: ``knn(x, queries, k) -> (float64 distances, ids)``
  exact and ascending; ``true_distances(x, queries, ids) -> float64``
  for each (query row, id) pair; ``control(x, queries, k) -> (float32
  distances, int32 ids)``, the control's answers (used by
  ``calibrate.py`` only). ``x`` is the corpus's sharded array.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Sequence

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(LookupError):
    """A name in BENCHMARK.json has no file of its own."""


def _find(dirs: Sequence[str], sub: str, name: str, ext: str) -> str:
    for d in dirs:
        path = os.path.join(d, sub, name + ext)
        if os.path.isfile(path):
            return path
    raise SpecError(f"no {sub}/{name}{ext} under {list(dirs)}")


def load_json(dirs: Sequence[str], sub: str, name: str) -> dict:
    with open(_find(dirs, sub, name, ".json")) as fh:
        return json.load(fh)


def load_module(dirs: Sequence[str], sub: str, name: str):
    """Import ``<dir>/<sub>/<name>.py`` (names may hold dots)."""
    path = _find(dirs, sub, name, ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_{sub}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, bench_path: str = BENCHMARK_JSON,
                 dirs: Sequence[str] = (BENCH_DIR,)):
        with open(bench_path) as fh:
            self.bench = json.load(fh)
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise SpecError(f"no workload {name!r} in {bench_path} "
                            f"(known: {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.dirs = tuple(dirs)
        self.chips = int(self.entry["chips"])
        self.conf = load_json(dirs, "configs", self.entry["config"])
        self.traffic = load_json(dirs, "traffic", self.entry["traffic"])
        self.family = load_module(dirs, "families", self.conf["family"])
        self.reference = load_module(dirs, "references",
                                     self.conf["reference"])
        self.work = (load_module(dirs, "work", self.conf["kernel"])
                     if self.conf.get("kernel") else None)

    def end_to_end(self):
        """The cell's end-to-end metric entries."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """``[(entry, reader module)]`` of the per-layer metrics this
        cell reports: those that list it, and those without a list
        whose end-to-end metric the cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if ("workloads" in m and self.name in m["workloads"]) or (
                    "workloads" not in m and m["moves"] in mine):
                out.append((m, load_module(self.dirs, "metrics",
                                           m["name"])))
        return out
