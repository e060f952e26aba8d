"""R3 — collective discipline.

Every mesh program in this repo goes through the
``raft_tpu.comms.comms`` veneer: it is the one spelling of
``shard_map``, ``axis_size`` and ``mark_varying``, where wire-dtype
policy is applied, and where the collective-payload accounting hooks.
A raw ``jax.lax`` collective (or a direct ``shard_map`` reference)
outside the veneer bypasses the wire policy and the accounting.

Checks:

- raw ``jax.lax`` collectives (``psum``/``pmax``/``all_gather``/
  ``ppermute``/``pvary``/…) anywhere but the veneer module, including
  the ``getattr(jax.lax, "pvary")`` feature-probe spelling;
- direct ``jax.experimental.shard_map`` imports / ``jax.shard_map``
  references outside the veneer;
- axis-name literals passed to veneer collectives that name no axis
  this module's meshes declare (a typo'd axis fails at trace time,
  but only on a code path a multi-chip test actually reaches).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from raft_tpu.analysis import astutil
from raft_tpu.analysis.core import Finding, Project, rule

VENEER_REL = "raft_tpu/comms/comms.py"

LAX_COLLECTIVES = {
    "psum", "pmax", "pmin", "pmean", "all_gather", "psum_scatter",
    "all_to_all", "ppermute", "pshuffle", "pbroadcast", "pvary",
    "pcast", "axis_index", "axis_size", "all_gather_invariant",
}

# veneer function name -> positional index of its axis argument
# (timed_dispatch is the PR 7 host-side timing shim: its axis names
# the mesh axis being timed, so a typo'd literal is the same latent
# bug an axis typo in a collective is)
VENEER_AXIS_POS = {
    "allreduce": 2, "bcast": 2, "reduce": 3, "allgather": 1,
    "allgather_wire": 1, "allgatherv": 2, "reducescatter": 2,
    "alltoall": 1, "device_send": 2, "device_recv": 2,
    "device_sendrecv": 2, "barrier": 0, "rank": 0, "size": 0,
    "mark_varying": 1, "timed_dispatch": 2,
    # graftwire quantized veneers (same positional axis slot as their
    # exact twins)
    "allreduce_quantized": 2, "reducescatter_quantized": 2,
    "allgather_quantized": 1,
}


def _comms_imports(tree: ast.AST) -> Set[str]:
    """Local names this module imported from raft_tpu.comms*."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith("raft_tpu.comms"):
            for a in node.names:
                names.add(a.asname or a.name)
    return names


def _known_axes(tree: ast.AST) -> Set[str]:
    """Axis names this module's meshes / specs / signatures declare."""
    axes: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            nm = (astutil.call_name(node) or "").split(".")[-1]
            if nm in ("Mesh", "AbstractMesh", "make_mesh"):
                for kw in node.keywords:
                    if kw.arg in ("axis_names", "axis"):
                        for c in ast.walk(kw.value):
                            if isinstance(c, ast.Constant) \
                                    and isinstance(c.value, str):
                                axes.add(c.value)
            if nm in ("P", "PartitionSpec"):
                for a in node.args:
                    if isinstance(a, ast.Constant) \
                            and isinstance(a.value, str):
                        axes.add(a.value)
            if nm == "Comms" and len(node.args) >= 2 \
                    and isinstance(node.args[1], ast.Constant) \
                    and isinstance(node.args[1].value, str):
                axes.add(node.args[1].value)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # `axis: str = "data"` parameter defaults declare vocabulary
            args = node.args
            pos = args.posonlyargs + args.args
            defaults = args.defaults
            for p, d in zip(pos[len(pos) - len(defaults):], defaults):
                if "axis" in p.arg and isinstance(d, ast.Constant) \
                        and isinstance(d.value, str):
                    axes.add(d.value)
            for p, d in zip(args.kwonlyargs, args.kw_defaults):
                if d is not None and "axis" in p.arg \
                        and isinstance(d, ast.Constant) \
                        and isinstance(d.value, str):
                    axes.add(d.value)
    return axes


def _axis_arg(call: ast.Call, leaf: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == "axis":
            return kw.value
    pos = VENEER_AXIS_POS[leaf]
    if pos < len(call.args):
        return call.args[pos]
    return None


@rule("R3", "collective-discipline")
def check_collectives(project: Project) -> Iterable[Finding]:
    """Raw jax.lax collectives / shard_map imports outside the comms
    veneer; axis-name literals that no mesh in the module declares."""
    out: List[Finding] = []
    for f in project.lib():
        if f.tree is None or f.rel == VENEER_REL:
            continue

        for node in ast.walk(f.tree):
            # raw lax collectives (and the getattr feature probe)
            if isinstance(node, ast.Attribute):
                nm = astutil.dotted(node)
                if nm and nm in {f"jax.lax.{c}" for c in LAX_COLLECTIVES} \
                        | {f"lax.{c}" for c in LAX_COLLECTIVES}:
                    out.append(Finding(
                        "R3", f.rel, node.lineno,
                        f"raw {nm} outside the comms veneer — route it "
                        "through raft_tpu.comms.comms so the wire "
                        "policy and payload accounting apply"))
            if isinstance(node, ast.Call):
                nm = astutil.call_name(node) or ""
                if nm == "getattr" and len(node.args) >= 2 \
                        and astutil.dotted(node.args[0]) in ("jax.lax",
                                                             "lax") \
                        and isinstance(node.args[1], ast.Constant) \
                        and node.args[1].value in LAX_COLLECTIVES:
                    out.append(Finding(
                        "R3", f.rel, node.lineno,
                        f"getattr(jax.lax, {node.args[1].value!r}) "
                        "feature probe outside the comms veneer — "
                        "collectives belong in raft_tpu.comms.comms"))
            # direct shard_map access
            if isinstance(node, ast.ImportFrom) and node.module \
                    and "shard_map" in node.module:
                out.append(Finding(
                    "R3", f.rel, node.lineno,
                    "direct jax.experimental.shard_map import — use "
                    "raft_tpu.comms.comms.shard_map"))
            if isinstance(node, ast.Attribute) \
                    and astutil.dotted(node) == "jax.shard_map":
                out.append(Finding(
                    "R3", f.rel, node.lineno,
                    "direct jax.shard_map reference — use "
                    "raft_tpu.comms.comms.shard_map"))

        # axis literal discipline on veneer calls
        veneer_names = _comms_imports(f.tree) & set(VENEER_AXIS_POS)
        axes = _known_axes(f.tree)
        if not axes:
            continue
        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call):
                continue
            nm = astutil.call_name(node) or ""
            leaf = nm.split(".")[-1]
            if leaf not in VENEER_AXIS_POS:
                continue
            # only calls provably bound to the comms veneer
            if not (nm.startswith("comms.") or leaf in veneer_names):
                continue
            arg = _axis_arg(node, leaf)
            if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str) and arg.value not in axes:
                out.append(Finding(
                    "R3", f.rel, node.lineno,
                    f"collective {leaf}() names axis {arg.value!r} but "
                    f"this module's meshes declare {sorted(axes)} — a "
                    "typo'd axis only fails on the multi-chip path"))
    return out
