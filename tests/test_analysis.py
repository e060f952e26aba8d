"""graftlint tests — the fixture corpus (one minimal violating + one
conforming sample per rule, so each rule is proven live: disable a
rule and its fixture test fails), the repo-wide "lint is clean" gate,
and the suppression-inventory snapshot (a new ``disable=`` pragma
anywhere in the tree must show up here, in review)."""

import pathlib

import pytest

from raft_tpu.analysis import RULES, lint_root, lint_texts
from raft_tpu.analysis.core import parse_pragma_items

ROOT = pathlib.Path(__file__).resolve().parents[1]


def rules_fired(report):
    return {f.rule for f in report.findings}


def lint_lib(src, rules, rel="raft_tpu/ops/sample.py"):
    return lint_texts({rel: src}, rules=rules)


# ---------------------------------------------------------------------------
# fixture corpus — VIOLATING / CONFORMING per rule
# ---------------------------------------------------------------------------

R0_VIOLATING = (
    "import os\n"          # unused import
    "x = 1 \n"             # trailing whitespace
)
R0_CONFORMING = "import os\n\nx = os.getpid()\n"

R1_VIOLATING = '''\
def _score_fn(queries, data, *, k: int):
    total = queries + data
    if total > 0:
        return total
    while queries:
        queries = queries - 1
    return total
'''
R1_CONFORMING = '''\
def _score_fn(queries, data, *, k: int):
    if queries.ndim == 2 and data is not None:
        return queries + data
    if k > 4:
        return data
    return queries
'''
R1_KEY_VIOLATING = '''\
def _plan(statics, arrays):
    key = ("ivf", [s for s in statics], float(arrays))
    return key
'''
R1_KEY_CONFORMING = '''\
def _plan(statics, arrays):
    key = ("ivf", tuple(sorted(statics)), len(arrays))
    return key
'''

R2_VIOLATING = '''\
import jax


def _step_fn(state):
    return state


def serve(state):
    step = jax.jit(_step_fn, donate_argnums=(0,))
    out = step(state)
    return out + state
'''
R2_CONFORMING = '''\
import jax


def _step_fn(state):
    return state


def serve(state):
    step = jax.jit(_step_fn, donate_argnums=(0,))
    state = step(state)
    return state
'''
R2_DECORATOR_VIOLATING = '''\
import functools

import jax


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter(buf, rows):
    return buf


def extend_all(buf, rows):
    out = _scatter(buf, rows)
    return out + buf
'''
R2_ARGNAMES_VIOLATING = '''\
import jax


def _step_fn(init_d, rows):
    return init_d


def serve(init_d, rows):
    step = jax.jit(_step_fn, donate_argnames=("init_d",))
    out = step(init_d, rows)
    return out + init_d
'''
R2_DONATE_KWARG = '''\
def extend(res, index, rows, donate=False):
    return index


def grow(res, index, rows):
    index = extend(res, index, rows, donate=True)
    return index, rows  # rows stays caller-owned — NOT a finding
'''

R3_VIOLATING = '''\
import jax


def merge(x, axis):
    return jax.lax.psum(x, axis)
'''
R3_CONFORMING = '''\
from raft_tpu.comms.comms import allreduce


def merge(x, axis):
    return allreduce(x, axis=axis)
'''
R3_AXIS_VIOLATING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import allgather


def merge(x):
    spec = P("data")
    return allgather(x, axis="dataa"), spec
'''
R3_AXIS_CONFORMING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import allgather


def merge(x):
    spec = P("data")
    return allgather(x, axis="data"), spec
'''

R4_VIOLATING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def run(x, interpret=False):
    n = x.shape[1]
    blocks = n // 512
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((8, 512), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, 512), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 512), x.dtype),
        interpret=interpret,
    )(x)
'''
R4_BUDGET_VIOLATING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def run(x, interpret=False):
    rows = 16384
    cols = 4096
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(x)
'''
R4_CONFORMING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def run(x, n, interpret=False):
    npad = -(-n // 512) * 512
    blocks = npad // 512
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((8, 512), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, 512), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 512), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(x)
'''

R4_SYMBOLIC_VIOLATING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def run(x, interpret=False):
    n = x.shape[0]
    rows = min(n, 65536)  # dynamic, but bounded by the cap
    cols = 4096
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(x)
'''
R4_SYMBOLIC_CONFORMING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def run(x, interpret=False):
    n = x.shape[0]
    rows = min(n, 256)  # dynamic, bounded well inside the budget
    cols = 512
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(x)
'''

R5_VIOLATING = '''\
import numpy as np


def _scan_fn(queries, data, *, k: int):
    hot = float(queries)
    host = np.asarray(data)
    return hot, host


def refresh(parts, dev):
    import jax

    out = []
    for p in parts:
        out.append(jax.device_put(p, dev))
    return out
'''
R5_CONFORMING = '''\
import numpy as np


def _scan_fn(queries, data, *, k: int):
    q = int(np.shape(queries)[0])
    return queries[:q] + data


def refresh(parts, dev):
    import jax

    return jax.device_put(list(parts), dev)
'''

# PR 5 scope extensions: R5 covers raft_tpu/serving/* and R1's
# cache-key discipline covers the batcher's coalescing keys
R5_SERVING_VIOLATING = '''\
def dispatch(batch):
    depth = batch.depth.item()
    return depth
'''
R1_SERVING_KEY_VIOLATING = '''\
def admit(executor, index, k, kw, handle):
    compat_key = (id(index), [k], float(kw))
    return SearchRequest(compat_key={"k": k}, handle=handle)
'''
R1_SERVING_KEY_CONFORMING = '''\
def admit(executor, index, k, kw, handle):
    compat_key = (id(index), k,
                  tuple(sorted((n, str(v)) for n, v in kw.items())))
    return SearchRequest(compat_key=compat_key, handle=handle)
'''

R7_SERVING_VIOLATING = '''\
import time


def pick_deadline(timeout_s):
    return time.monotonic() + timeout_s


def stamp():
    return time.time()
'''
R7_SERVING_CONFORMING = '''\
import time


class MonotonicClock:
    def now(self):
        return time.monotonic()


class WallClock:
    def now(self):
        return time.time()


def pick_deadline(clock, timeout_s):
    return clock.now() + timeout_s


def nap(delay_s):
    time.sleep(delay_s)    # sleeping reads no clock
'''
R7_BARE_IMPORT_VIOLATING = '''\
from time import monotonic


def stamp():
    return monotonic()
'''
R7_EVASION_VIOLATING = '''\
import time as t
from time import time
from time import perf_counter as pc


def three_ways():
    return t.monotonic() + time() + pc()
'''
R7_LOCAL_NAME_CONFORMING = '''\
def use_local(time, monotonic):
    return time() + monotonic()    # locals, not the time module
'''

# PR 7 scope extensions: datetime is a wall-clock read too (span /
# SLO call sites must stay in the injectable clock's domain), and the
# comms timed-dispatch shim joins R3's axis-literal discipline
R7_DATETIME_VIOLATING = '''\
import datetime
from datetime import datetime as dt


def stamp_span():
    return datetime.datetime.now().timestamp()


def stamp_bare():
    return dt.utcnow()


def day():
    return datetime.date.today()
'''
R7_DATETIME_CONFORMING = '''\
import datetime


def render(ts):
    # transforming an existing timestamp VALUE reads no clock
    return datetime.datetime.fromtimestamp(ts).isoformat()


def span_times(clock):
    t0 = clock.now()
    return t0, clock.now()
'''
R3_TIMED_DISPATCH_VIOLATING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import timed_dispatch


def dispatch(thunk):
    spec = P("data")
    return timed_dispatch("knn", thunk, "dataa"), spec
'''
R3_TIMED_DISPATCH_CONFORMING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import timed_dispatch


def dispatch(thunk):
    spec = P("data")
    return timed_dispatch("knn", thunk, "data"), spec
'''

# graftwire: the quantized-collective veneers join R3's axis-literal
# discipline at the same positional slots as their exact twins
R3_QUANTIZED_VIOLATING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import (
    Op,
    allgather_quantized,
    allreduce_quantized,
    reducescatter_quantized,
)


def reduce_sums(sums, coarse):
    spec = P("data")
    s = allreduce_quantized(sums, Op.SUM, "dataa", wire_dtype="int8")
    m = reducescatter_quantized(sums, Op.SUM, axis="datb")
    g = allgather_quantized(coarse, "datc", "int8")
    return s, m, g, spec
'''
R3_QUANTIZED_CONFORMING = '''\
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import (
    Op,
    allgather_quantized,
    allreduce_quantized,
    reducescatter_quantized,
)


def reduce_sums(sums, coarse):
    spec = P("data")
    s = allreduce_quantized(sums, Op.SUM, "data", wire_dtype="int8")
    m = reducescatter_quantized(sums, Op.SUM, axis="data")
    g = allgather_quantized(coarse, "data", "int8")
    return s, m, g, spec
'''

# graftwire: R1's key discipline extends to mesh_key-spelled builders —
# the 2-D mesh identity tuple feeds every dist plan key
R1_MESH_KEY_VIOLATING = '''\
def _mesh_key(comms):
    mesh = comms.mesh
    return ("mesh", comms.axis, [d.id for d in mesh.devices.flat],
            int(mesh.devices.size))
'''
R1_MESH_KEY_CONFORMING = '''\
def _mesh_key(comms):
    mesh = comms.mesh
    return ("mesh", comms.axis, tuple(mesh.axis_names),
            tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))
'''

R6_OPS_VIOLATING = '''\
from jax.experimental import pallas as pl


def my_kernel_entry(x, *, interpret: bool = False):
    return pl.pallas_call(lambda x_ref, o_ref: None)(x)
'''
R6_TEST_CONFORMING = '''\
def test_kernel():
    from raft_tpu.ops.sample import my_kernel_entry

    my_kernel_entry(None, interpret=True)
'''


class TestFixtureCorpus:
    """Each rule fires on its violating sample and stays quiet on the
    conforming one — delete a rule from the registry and the
    corresponding test fails."""

    def test_r0(self):
        bad = lint_lib(R0_VIOLATING, ["R0"])
        msgs = [f.message for f in bad.findings]
        assert any("unused import" in m for m in msgs), msgs
        assert any("trailing whitespace" in m for m in msgs), msgs
        assert lint_lib(R0_CONFORMING, ["R0"]).ok

    def test_r1_tracer_control_flow(self):
        bad = lint_lib(R1_VIOLATING, ["R1"])
        assert rules_fired(bad) == {"R1"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "`if`" in msgs and "`while`" in msgs, msgs
        assert lint_lib(R1_CONFORMING, ["R1"]).ok

    def test_r1_cache_key(self):
        bad = lint_lib(R1_KEY_VIOLATING, ["R1"])
        msgs = " ".join(f.message for f in bad.findings)
        assert "unhashable" in msgs and "float()" in msgs, msgs
        assert lint_lib(R1_KEY_CONFORMING, ["R1"]).ok

    def test_r2(self):
        bad = lint_lib(R2_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}
        assert "read after being donated" in bad.findings[0].message
        assert lint_lib(R2_CONFORMING, ["R2"]).ok

    def test_r2_decorator_and_argnames_forms(self):
        bad = lint_lib(R2_DECORATOR_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}, [
            f.render() for f in bad.findings]
        bad = lint_lib(R2_ARGNAMES_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}, [
            f.render() for f in bad.findings]

    def test_r2_donate_kwarg_donates_only_the_index(self):
        # second positional is donated; later args stay caller-owned
        assert lint_lib(R2_DONATE_KWARG, ["R2"]).ok
        bad = lint_lib(R2_DONATE_KWARG.replace(
            "return index, rows", "return index, index")
            .replace("index = extend", "out = extend"), ["R2"])
        assert rules_fired(bad) == {"R2"}
        # keyword spelling of the same bug is caught too
        bad = lint_lib(R2_DONATE_KWARG.replace(
            "return index, rows", "return index, index")
            .replace("index = extend(res, index, rows, donate=True)",
                     "out = extend(res, index=index, rows=rows, "
                     "donate=True)"), ["R2"])
        assert rules_fired(bad) == {"R2"}

    def test_r3_raw_collective(self):
        bad = lint_lib(R3_VIOLATING, ["R3"])
        assert rules_fired(bad) == {"R3"}
        assert "jax.lax.psum" in bad.findings[0].message
        assert lint_lib(R3_CONFORMING, ["R3"]).ok

    def test_r3_axis_name(self):
        bad = lint_lib(R3_AXIS_VIOLATING, ["R3"])
        assert rules_fired(bad) == {"R3"}
        assert "'dataa'" in bad.findings[0].message
        assert lint_lib(R3_AXIS_CONFORMING, ["R3"]).ok

    def test_r3_quantized_veneers(self):
        bad = lint_lib(R3_QUANTIZED_VIOLATING, ["R3"])
        assert rules_fired(bad) == {"R3"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "allreduce_quantized" in msgs, msgs
        assert "reducescatter_quantized" in msgs, msgs
        assert "allgather_quantized" in msgs, msgs
        assert lint_lib(R3_QUANTIZED_CONFORMING, ["R3"]).ok

    def test_r1_mesh_key_discipline(self):
        bad = lint_lib(R1_MESH_KEY_VIOLATING, ["R1"])
        msgs = " ".join(f.message for f in bad.findings)
        assert "unhashable" in msgs and "int()" in msgs, msgs
        assert lint_lib(R1_MESH_KEY_CONFORMING, ["R1"]).ok

    def test_r4_missing_params_and_grid(self):
        bad = lint_lib(R4_VIOLATING, ["R4"])
        msgs = " ".join(f.message for f in bad.findings)
        assert "without compiler_params" in msgs, msgs
        assert "not padded up to the divisor" in msgs, msgs
        assert lint_lib(R4_CONFORMING, ["R4"]).ok

    def test_r4_static_vmem_budget(self):
        bad = lint_lib(R4_BUDGET_VIOLATING, ["R4"])
        msgs = " ".join(f.message for f in bad.findings)
        assert "exceeds" in msgs and "MiB" in msgs, msgs

    def test_r4_symbolic_upper_bound(self):
        # a dim that doesn't const-fold (min(n, CAP)) no longer
        # escapes the budget check — the CAP bounds it
        bad = lint_lib(R4_SYMBOLIC_VIOLATING, ["R4"])
        msgs = " ".join(f.message for f in bad.findings)
        assert "upper bound" in msgs and "exceeds" in msgs, msgs
        assert lint_lib(R4_SYMBOLIC_CONFORMING, ["R4"]).ok

    def test_r5(self):
        bad = lint_lib(R5_VIOLATING, ["R5"])
        assert rules_fired(bad) == {"R5"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "float()" in msgs
        assert "np.asarray" in msgs
        assert "device_put inside a python loop" in msgs
        assert lint_lib(R5_CONFORMING, ["R5"]).ok

    def test_r5_covers_serving_modules(self):
        bad = lint_lib(R5_SERVING_VIOLATING, ["R5"],
                       rel="raft_tpu/serving/sample.py")
        assert rules_fired(bad) == {"R5"}
        assert ".item()" in bad.findings[0].message
        # the same source outside the hot set stays quiet
        assert lint_lib(R5_SERVING_VIOLATING, ["R5"],
                        rel="raft_tpu/io/sample.py").ok

    def test_r1_serving_compat_key(self):
        bad = lint_lib(R1_SERVING_KEY_VIOLATING, ["R1"],
                       rel="raft_tpu/serving/sample.py")
        msgs = " ".join(f.message for f in bad.findings)
        assert "unhashable" in msgs and "float()" in msgs, msgs
        assert lint_lib(R1_SERVING_KEY_CONFORMING, ["R1"],
                        rel="raft_tpu/serving/sample.py").ok

    def test_r7_clock_discipline(self):
        bad = lint_lib(R7_SERVING_VIOLATING, ["R7"],
                       rel="raft_tpu/serving/sample.py")
        assert rules_fired(bad) == {"R7"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "time.monotonic" in msgs and "time.time" in msgs, msgs
        assert "injectable clock" in msgs
        assert lint_lib(R7_SERVING_CONFORMING, ["R7"],
                        rel="raft_tpu/serving/sample.py").ok
        # from-imports of clock functions are still clock reads
        bad = lint_lib(R7_BARE_IMPORT_VIOLATING, ["R7"],
                       rel="raft_tpu/serving/sample.py")
        assert rules_fired(bad) == {"R7"}
        # evasion routes: aliased module, `from time import time`,
        # aliased from-import — all three fire
        bad = lint_lib(R7_EVASION_VIOLATING, ["R7"],
                       rel="raft_tpu/serving/sample.py")
        assert len(bad.findings) == 3, [f.render() for f in bad.findings]
        # a local variable that happens to be named `time` stays exempt
        assert lint_lib(R7_LOCAL_NAME_CONFORMING, ["R7"],
                        rel="raft_tpu/serving/sample.py").ok
        # the same sources outside raft_tpu/serving/ stay quiet
        assert lint_lib(R7_SERVING_VIOLATING, ["R7"],
                        rel="raft_tpu/ops/sample.py").ok

    def test_r5_r7_cover_graftgauge_sampler_module(self):
        """PR 8 satellite: the hot scope reaches the new graftgauge
        sampler module by its real path — a host sync or a bare clock
        read landing in ``raft_tpu/serving/gauge.py`` is a finding,
        not a blind spot (the shipped module itself lints clean: its
        fetches are scrape-time by contract and its timestamps come
        from the batcher's injectable clock)."""
        sampler_sync = (
            "def pump(handles):\n"
            "    return [h.depth.item() for h in handles]\n"
        )
        bad = lint_lib(sampler_sync, ["R5"],
                       rel="raft_tpu/serving/gauge.py")
        assert rules_fired(bad) == {"R5"}
        sampler_clock = (
            "import time\n"
            "\n"
            "\n"
            "def shadow_stamp():\n"
            "    return time.monotonic()\n"
        )
        bad = lint_lib(sampler_clock, ["R7"],
                       rel="raft_tpu/serving/gauge.py")
        assert rules_fired(bad) == {"R7"}
        # and the conforming discipline the module actually uses
        ok = (
            "def shadow_stamp(clock):\n"
            "    return clock.now()\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/gauge.py").ok

    def test_r5_r7_cover_graftfleet_modules(self):
        """PR 12 satellite: the hot scope reaches BOTH new graftfleet
        serving modules by their real paths — a host sync landing in
        the continuous scheduler or a bare clock read in the
        federation aggregator is a finding, not a blind spot (the
        shipped modules lint clean: timestamps come from injected
        clocks, the capture's ``time.sleep`` is the documented
        duration exemption, and federation is urllib + dict work)."""
        cont_sync = (
            "def tick(planes):\n"
            "    return [p.total.item() for p in planes]\n"
        )
        bad = lint_lib(cont_sync, ["R5"],
                       rel="raft_tpu/serving/continuous.py")
        assert rules_fired(bad) == {"R5"}
        cont_clock = (
            "import time\n"
            "\n"
            "\n"
            "def next_tick_due():\n"
            "    return time.monotonic()\n"
        )
        bad = lint_lib(cont_clock, ["R7"],
                       rel="raft_tpu/serving/continuous.py")
        assert rules_fired(bad) == {"R7"}
        fed_clock = (
            "import time\n"
            "\n"
            "\n"
            "def replica_age(scraped_at):\n"
            "    return time.time() - scraped_at\n"
        )
        bad = lint_lib(fed_clock, ["R7"],
                       rel="raft_tpu/serving/federation.py")
        assert rules_fired(bad) == {"R7"}
        fed_sync = (
            "def merge_planes(planes):\n"
            "    return sum(p.sum().item() for p in planes)\n"
        )
        bad = lint_lib(fed_sync, ["R5"],
                       rel="raft_tpu/serving/federation.py")
        assert rules_fired(bad) == {"R5"}
        # the conforming discipline both modules actually use:
        # injected-clock stamps, durations slept not read
        ok = (
            "import time\n"
            "\n"
            "\n"
            "def tick(clock, seconds):\n"
            "    t = clock.now()\n"
            "    time.sleep(seconds)\n"
            "    return t\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/continuous.py").ok
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/federation.py").ok

    def test_r5_r7_cover_graftflight_module(self):
        """PR 11 satellite: the hot scope reaches the new graftflight
        flight-recorder module by its real path — a host sync or a
        bare clock read landing in ``raft_tpu/serving/flight.py`` is a
        finding, not a blind spot (the shipped module itself lints
        clean: its timestamps come from the injected clock, its only
        wall-time touch is the capture's exempt ``time.sleep``, and
        the bundle reads registries, never device arrays)."""
        flight_sync = (
            "def check(handles):\n"
            "    return [h.depth.item() for h in handles]\n"
        )
        bad = lint_lib(flight_sync, ["R5"],
                       rel="raft_tpu/serving/flight.py")
        assert rules_fired(bad) == {"R5"}
        flight_clock = (
            "import time\n"
            "\n"
            "\n"
            "def incident_stamp():\n"
            "    return time.monotonic()\n"
        )
        bad = lint_lib(flight_clock, ["R7"],
                       rel="raft_tpu/serving/flight.py")
        assert rules_fired(bad) == {"R7"}
        # the conforming discipline the module actually uses: clock
        # injection for stamps, time.sleep (a duration) for captures
        ok = (
            "import time\n"
            "\n"
            "\n"
            "def capture(clock, seconds):\n"
            "    t = clock.now()\n"
            "    time.sleep(seconds)\n"
            "    return t\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/flight.py").ok
        # core/profiling.py is OFFLINE host-side parsing — outside the
        # hot scopes by design (it must never run on a dispatch path);
        # prove the scope boundary sits where the docs say it does
        assert lint_lib(flight_clock, ["R7"],
                        rel="raft_tpu/core/profiling.py").ok

    def test_r5_r7_cover_graftledger_module(self):
        """PR 13 satellite: the hot scopes reach ``core/memwatch.py``
        by its real path — the watermark sample runs on the executor's
        dispatch path, so a host sync there taxes every search, and a
        bare clock read would split the scrape surface across time
        domains (the shipped module lints clean: it is shape/dtype
        arithmetic plus ``memory_stats()`` backend introspection, and
        keeps no timestamps at all)."""
        ledger_sync = (
            "def sample_dispatch(planes):\n"
            "    return sum(p.sum().item() for p in planes)\n"
        )
        bad = lint_lib(ledger_sync, ["R5"],
                       rel="raft_tpu/core/memwatch.py")
        assert rules_fired(bad) == {"R5"}
        ledger_clock = (
            "import time\n"
            "\n"
            "\n"
            "def sample_stamp():\n"
            "    return time.monotonic()\n"
        )
        bad = lint_lib(ledger_clock, ["R7"],
                       rel="raft_tpu/core/memwatch.py")
        assert rules_fired(bad) == {"R7"}
        # the conforming discipline the module actually uses: pure
        # metadata arithmetic, no clocks, no array fetches
        ok = (
            "def shard_bytes(shape, itemsize):\n"
            "    b = itemsize\n"
            "    for s in shape:\n"
            "        b *= s\n"
            "    return b\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/core/memwatch.py").ok
        # the scope boundary: other core modules stay OUTSIDE both
        # rules (profiling.py's R7 boundary is proven above; prove
        # the R5 side the same way — memwatch is the one core file
        # beyond executor.py on the dispatch path)
        assert lint_lib(ledger_sync, ["R5"],
                        rel="raft_tpu/core/serialize.py").ok
        assert lint_lib(ledger_clock, ["R7"],
                        rel="raft_tpu/core/serialize.py").ok

    def test_r5_r7_cover_graftcast_prefetch_module(self):
        """PR 18 satellite: the hot scopes reach the new graftcast
        prefetcher module by its real path — a bare clock read there
        would re-couple the lead-time pacing to the wall clock (the
        forecast must replay deterministically under the ManualClock
        fault suite), and a device-array fetch would stall the stage
        DMA behind serving's dispatch stream (the shipped module
        lints clean: pacing lives in the TierManager's injected
        clock, slot truth comes from the host-side list mirrors, and
        eviction recency is a logical sequence number)."""
        prefetch_clock = (
            "import time\n"
            "\n"
            "\n"
            "def lead_due(last_epoch_at, lead_s):\n"
            "    return time.monotonic() - last_epoch_at >= lead_s\n"
        )
        bad = lint_lib(prefetch_clock, ["R7"],
                       rel="raft_tpu/serving/prefetch.py")
        assert rules_fired(bad) == {"R7"}
        prefetch_sync = (
            "def staged_rows(planes):\n"
            "    return [p.sum().item() for p in planes]\n"
        )
        bad = lint_lib(prefetch_sync, ["R5"],
                       rel="raft_tpu/serving/prefetch.py")
        assert rules_fired(bad) == {"R5"}
        # the conforming discipline the module actually uses: logical
        # recency, injected pacing, host-side slot mirrors
        ok = (
            "def evict_candidate(row_age, active):\n"
            "    best = None\n"
            "    for row in active:\n"
            "        if best is None or row_age[row] < row_age[best]:\n"
            "            best = row\n"
            "    return best\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/prefetch.py").ok

    def test_r5_covers_tier_scan_cold_engines(self):
        """PR 18 satellite: the R5 hot scope reaches the tiered cold
        engines by their real path — the list-major cold scan runs
        per dispatch, so one stray ``.item()`` (say, reading a cold
        slot id off the device map instead of the host mirror) taxes
        every tiered search exactly like an executor-side sync."""
        cold_sync = (
            "def cold_slot_of(cold_slot_map, lid):\n"
            "    return cold_slot_map[lid].item()\n"
        )
        bad = lint_lib(cold_sync, ["R5"],
                       rel="raft_tpu/ops/tier_scan.py")
        assert rules_fired(bad) == {"R5"}
        # the conforming discipline the engines actually use: slot
        # arithmetic on host mirrors, device work stays traced
        ok = (
            "def cold_slot_of(cold_lists, lid):\n"
            "    for slot, cl in enumerate(cold_lists):\n"
            "        if cl == lid:\n"
            "            return slot\n"
            "    return -1\n"
        )
        assert lint_lib(ok, ["R5"],
                        rel="raft_tpu/ops/tier_scan.py").ok

    def test_r7_datetime_clock_reads(self):
        """PR 7: datetime.now()/utcnow()/date.today() are wall-clock
        reads — module-dotted and from-import spellings both fire;
        fromtimestamp (a value transform) stays exempt."""
        bad = lint_lib(R7_DATETIME_VIOLATING, ["R7"],
                       rel="raft_tpu/serving/sample.py")
        assert rules_fired(bad) == {"R7"}
        assert len(bad.findings) == 3, [f.render() for f in bad.findings]
        assert lint_lib(R7_DATETIME_CONFORMING, ["R7"],
                        rel="raft_tpu/serving/sample.py").ok
        # outside the serving scope: quiet, like the time-module rule
        assert lint_lib(R7_DATETIME_VIOLATING, ["R7"],
                        rel="raft_tpu/ops/sample.py").ok

    def test_r3_timed_dispatch_axis_literal(self):
        """PR 7: the comms timed-dispatch shim is on R3's veneer
        allowlist — a typo'd axis literal at its call site is the same
        latent multi-chip bug as one inside a collective."""
        bad = lint_lib(R3_TIMED_DISPATCH_VIOLATING, ["R3"])
        assert rules_fired(bad) == {"R3"}
        assert "'dataa'" in bad.findings[0].message
        assert lint_lib(R3_TIMED_DISPATCH_CONFORMING, ["R3"]).ok

    def test_r6(self):
        bad = lint_texts({"raft_tpu/ops/sample.py": R6_OPS_VIOLATING},
                         rules=["R6"])
        assert rules_fired(bad) == {"R6"}
        assert "no interpret=True call" in bad.findings[0].message
        ok = lint_texts({"raft_tpu/ops/sample.py": R6_OPS_VIOLATING,
                         "tests/test_sample.py": R6_TEST_CONFORMING},
                        rules=["R6"])
        assert ok.ok


class TestDataflow:
    """The traced-name machinery R1/R5 stand on."""

    @staticmethod
    def _traced(src):
        import ast

        from raft_tpu.analysis import astutil

        fn = ast.parse(src).body[0]
        return astutil.traced_names(fn)

    def test_seed_convention(self):
        traced = self._traced(
            "def _f(queries, data, init_d=None, *, k: int, metric): pass")
        assert traced == {"queries", "data", "init_d"}

    def test_annotated_positionals_are_static(self):
        # annotated params, 'res', and 'self' are never tracers
        traced = self._traced(
            "def _f(mode: str, queries, res, self=None): pass")
        assert traced == {"queries"}

    def test_metadata_launders(self):
        traced = self._traced(
            "def _f(q):\n"
            "    n = q.shape[0]\n"
            "    d = len(q)\n"
            "    v = q + 1\n"
            "    pass\n")
        assert "n" not in traced and "d" not in traced
        assert "v" in traced and "q" in traced

    def test_rebind_to_static_clears(self):
        traced = self._traced(
            "def _f(q):\n"
            "    x = q * 2\n"
            "    x = 3\n"
            "    pass\n")
        assert "x" not in traced

    def test_value_names_identity_checks_exempt(self):
        import ast

        from raft_tpu.analysis import astutil

        expr = ast.parse("x is None or y.ndim == 2", mode="eval").body
        assert astutil.value_names(expr) == set()
        expr = ast.parse("x > 0", mode="eval").body
        assert astutil.value_names(expr) == {"x"}

    def test_jit_decorator_statics(self):
        import ast

        from raft_tpu.analysis import astutil

        fn = ast.parse(
            "@partial(jax.jit, static_argnames=('k',))\n"
            "def _f(q, k): pass").body[0]
        statics = astutil.jit_static_names(fn)
        assert statics == {"k"}
        assert astutil.traced_names(fn, statics) == {"q"}


class TestSuppressions:
    def test_pragma_silences_with_reason(self):
        src = R3_VIOLATING.replace(
            "return jax.lax.psum(x, axis)",
            "return jax.lax.psum(x, axis)"
            "  # graftlint: disable=R3(fixture: exercising suppression)")
        rep = lint_lib(src, ["R3"])
        assert rep.ok
        assert len(rep.suppressed) == 1
        assert rep.suppressed[0][1] == "fixture: exercising suppression"

    def test_pragma_without_reason_is_a_finding(self):
        src = R3_VIOLATING.replace(
            "return jax.lax.psum(x, axis)",
            "return jax.lax.psum(x, axis)  # graftlint: disable=R3")
        rep = lint_lib(src, ["R0", "R3"])
        assert any("carries no reason" in f.message for f in rep.findings)

    def test_unused_pragma_is_a_finding(self):
        src = R3_CONFORMING.replace(
            "return allreduce(x, axis=axis)",
            "return allreduce(x, axis=axis)"
            "  # graftlint: disable=R3(stale)")
        rep = lint_lib(src, ["R0", "R3"])
        assert any("unused suppression" in f.message for f in rep.findings)

    def test_pragma_in_docstring_is_not_a_pragma(self):
        src = ('def f():\n'
               '    """Example: # graftlint: disable=R3(quoted)."""\n'
               '    return 0\n')
        rep = lint_lib(src, ["R0"])
        assert rep.ok and not rep.suppressions

    def test_trailing_pragma_on_continuation_line(self):
        """A pragma trailing the *second* physical line of a multi-line
        statement must still suppress the finding (which anchors to the
        statement's first line)."""
        src = (
            "import jax\n"
            "\n"
            "\n"
            "def merge(x, axis):\n"
            "    return jax.lax.psum(\n"
            "        x, axis)"
            "  # graftlint: disable=R3(fixture: continuation line)\n")
        rep = lint_lib(src, ["R0", "R3"])
        assert rep.ok, [f.render() for f in rep.findings]
        assert len(rep.suppressed) == 1

    def test_unknown_rule_id_is_a_finding(self):
        src = ("x = 1"
               "  # graftlint: disable=R77(typo for a real rule)\n")
        rep = lint_lib(src, ["R0"])
        assert any("unknown rule 'R77'" in f.message
                   for f in rep.findings), [
            f.render() for f in rep.findings]

    def test_rule_filtered_run_has_no_pragma_hygiene_leak(self):
        """ops-guard style runs (rules=[R6]) must not surface R0
        pragma-hygiene findings from unrelated files."""
        src = "x = 1  # graftlint: disable=R77\n"
        rep = lint_lib(src, ["R6"])
        assert rep.ok
        rep = lint_lib(src, ["R0"])
        assert any("carries no reason" in f.message for f in rep.findings)

    def test_parser_handles_parens_and_lists(self):
        items, bad = parse_pragma_items(
            "R1(keys are O(1) hashable), R5(bounded to O(block))")
        assert not bad
        assert items == [("R1", "keys are O(1) hashable"),
                         ("R5", "bounded to O(block)")]


class TestRepoWide:
    """The CI gate, in-process: the live tree must lint clean, and the
    suppression inventory is snapshot — adding a pragma anywhere means
    updating this list in the same diff."""

    # (path, rule, reason) for every pragma in the tree — KEEP SORTED
    EXPECTED_SUPPRESSIONS = [
        # the serving split (bucketed and ragged) fetches a call's
        # output tiles once, in one shared helper, instead of
        # dispatching per-request device slices whose micro-programs
        # would recompile per load shape
        ("raft_tpu/core/executor.py", "R5",
         "the serving split is host-side by design: one batched fetch "
         "of a call's output tiles replaces per-request device-slice "
         "programs; every serving caller reads its results on the "
         "host"),
        ("raft_tpu/distributed/ivf.py", "R5",
         "streaming deal: per-block puts bound build staging to "
         "O(block)"),
        # the byte-typed streaming build: each chunk crosses to every
        # list shard once, the same bound as the deal's
        ("raft_tpu/distributed/ivf.py", "R5",
         "streaming scatter: one replicated put per chunk bounds "
         "build staging to O(chunk)"),
        ("raft_tpu/serving/harness.py", "R5",
         "device-free test shim: inputs are host arrays by contract"),
        # PR 9: FakeExecutor grew the ragged dispatch entry — same
        # device-free shim, second suppression with the same reason
        ("raft_tpu/serving/harness.py", "R5",
         "device-free test shim: inputs are host arrays by contract"),
        # PR 19: R8 guarded-by seeding — two benign races kept by
        # design, each with the reason the race is safe
        ("raft_tpu/core/tracing.py", "R8",
         "deque reference never rebinds; maxlen is immutable"),
        ("raft_tpu/serving/batcher.py", "R8",
         "benign racy fast-fail; the authoritative check re-runs "
         "under _cond before enqueue"),
    ]

    @pytest.fixture(scope="class")
    def report(self):
        return lint_root(ROOT)

    def test_registry_is_complete(self):
        assert sorted(RULES) == ["R0", "R1", "R2", "R3", "R4", "R5",
                                 "R6", "R7", "R8", "R9"]

    def test_repo_lints_clean(self, report):
        assert report.ok, "\n" + "\n".join(
            f.render() for f in report.findings)

    def test_suppression_inventory_snapshot(self, report):
        got = sorted((s.path, s.rule, s.reason)
                     for s in report.suppressions)
        assert got == sorted(self.EXPECTED_SUPPRESSIONS), (
            "suppression inventory changed — review the new/removed "
            f"pragmas and update the snapshot:\n{got}")

    def test_every_suppression_is_used(self, report):
        stale = [s for s in report.suppressions if not s.used]
        assert not stale, stale

    def test_suppression_inventory_json_shape(self, report):
        """``--list-suppressions --format=json`` and the
        ``ci/graftlint_report.json`` artifact expose the same
        ``[path, rule, reason]`` rows this snapshot pins."""
        rows = report.suppression_inventory()
        assert rows == sorted(list(t)
                              for t in self.EXPECTED_SUPPRESSIONS)
        assert report.to_dict()["suppression_inventory"] == rows


# PR 9 scope proofs: the ragged plan/kernel code paths are inside
# R1/R4/R5's reach — a hazard landing in the new code is a finding,
# not a blind spot (the shipped modules themselves lint clean).

R1_RAGGED_FN_VIOLATING = '''\
def _search_ragged_fn(queries, row_probes, centers, *, n_probes: int,
                      k: int):
    probes = queries + centers
    if row_probes > 0:
        probes = probes + 1
    return probes
'''
R1_RAGGED_KEY_VIOLATING = '''\
def _plan_ragged(statics, specs):
    ragged_key = ("ivf_flat_ragged", [s for s in specs],
                  float(statics))
    return ragged_key
'''
R1_RAGGED_KEY_CONFORMING = '''\
def _plan_ragged(statics, specs):
    ragged_key = ("ivf_flat_ragged", tuple(sorted(specs)),
                  len(statics))
    return ragged_key
'''
R4_RAGGED_KERNEL_VIOLATING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ragged_scan_kernel(u_ref, q_ref, o_ref):
    o_ref[:] = q_ref[:]


def scan_ragged(uniq, q, interpret=False):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i, u: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, u: (i, 0)),
    )
    return pl.pallas_call(
        _ragged_scan_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((32, 128), q.dtype),
        interpret=interpret,
    )(uniq, q)
'''
R5_RAGGED_PACKING_VIOLATING = '''\
def search_ragged(self, index, blocks, ks):
    sizes = [int(b.sum().item()) for b in blocks]
    return sizes
'''


class TestRaggedScopeProofs:
    """PR 9 satellite: R1/R4/R5 fire on ragged-plan/kernel-shaped
    hazards at the real module paths the ragged path lives in."""

    def test_r1_traced_branch_in_ragged_body(self):
        bad = lint_lib(R1_RAGGED_FN_VIOLATING, ["R1"],
                       rel="raft_tpu/neighbors/ivf_flat.py")
        assert rules_fired(bad) == {"R1"}
        assert "row_probes" in " ".join(
            f.message for f in bad.findings)

    def test_r1_ragged_packing_key_discipline(self):
        bad = lint_lib(R1_RAGGED_KEY_VIOLATING, ["R1"],
                       rel="raft_tpu/core/executor.py")
        msgs = " ".join(f.message for f in bad.findings)
        assert "unhashable" in msgs and "float()" in msgs, msgs
        assert lint_lib(R1_RAGGED_KEY_CONFORMING, ["R1"],
                        rel="raft_tpu/core/executor.py").ok

    def test_r4_ragged_kernel_needs_budget(self):
        bad = lint_lib(R4_RAGGED_KERNEL_VIOLATING, ["R4"],
                       rel="raft_tpu/ops/ivf_scan.py")
        assert "R4" in rules_fired(bad)
        assert any("vmem" in f.message.lower()
                   for f in bad.findings), [
            f.render() for f in bad.findings]

    def test_r5_host_sync_in_ragged_packing(self):
        bad = lint_lib(R5_RAGGED_PACKING_VIOLATING, ["R5"],
                       rel="raft_tpu/core/executor.py")
        assert rules_fired(bad) == {"R5"}
        assert ".item()" in bad.findings[0].message
        # the same source outside the hot set stays quiet
        assert lint_lib(R5_RAGGED_PACKING_VIOLATING, ["R5"],
                        rel="raft_tpu/label/sample.py").ok


# PR 10 scope proof: the fused BQ kernel (conditional-DMA pallas_call
# with an ANY-space operand — ops/bq_scan.py) is inside R4's reach: an
# undeclared VMEM budget on a bq_scan-shaped kernel is a finding, not
# a blind spot (the shipped module itself lints clean, suppression
# snapshot unchanged).

R4_BQ_KERNEL_VIOLATING = '''\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bq_kernel(u_ref, q_ref, data_ref, o_ref, vec, sem):
    o_ref[:] = q_ref[:]


def bq_scan(uniq, q, data, interpret=False):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4,),
        in_specs=[
            pl.BlockSpec((8, 128), lambda i, u: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((8, 128), lambda i, u: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 512, 128), jax.numpy.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _bq_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((32, 128), q.dtype),
        interpret=interpret,
    )(uniq, q, data)
'''


class TestBqScanScopeProof:
    def test_r4_bq_kernel_needs_budget(self):
        bad = lint_lib(R4_BQ_KERNEL_VIOLATING, ["R4"],
                       rel="raft_tpu/ops/bq_scan.py")
        assert "R4" in rules_fired(bad)
        assert any("vmem" in f.message.lower()
                   for f in bad.findings), [
            f.render() for f in bad.findings]


class TestGrafttierScopeProofs:
    """PR 14 satellite: the lint scopes reach BOTH new grafttier
    modules by their real paths — a budget-less pallas_call in the
    tiered scan, a host sync in either module, or a bare clock read
    in the placement policy is a finding, not a blind spot (the
    shipped modules lint clean: the kernel declares its VMEM budget
    from the shared footprint model, the scan is pure device work,
    and the manager's epochs fire from an injected clock)."""

    def test_r4_covers_tier_scan(self):
        bad = lint_lib(R4_VIOLATING, ["R4"],
                       rel="raft_tpu/ops/tier_scan.py")
        msgs = " ".join(f.message for f in bad.findings)
        assert "without compiler_params" in msgs, msgs
        assert lint_lib(R4_CONFORMING, ["R4"],
                        rel="raft_tpu/ops/tier_scan.py").ok

    def test_r5_covers_tier_scan_and_placement(self):
        tier_sync = (
            "def search_tiered(handles):\n"
            "    return [h.best.item() for h in handles]\n"
        )
        bad = lint_lib(tier_sync, ["R5"],
                       rel="raft_tpu/ops/tier_scan.py")
        assert rules_fired(bad) == {"R5"}
        bad = lint_lib(tier_sync, ["R5"],
                       rel="raft_tpu/serving/placement.py")
        assert rules_fired(bad) == {"R5"}
        # device_put in a python loop — the per-swap antipattern the
        # fixed-width batched swap exists to avoid
        swap_loop = (
            "import jax\n"
            "\n"
            "\n"
            "def search_swap(blocks, devs):\n"
            "    out = []\n"
            "    for b in blocks:\n"
            "        out.append(jax.device_put(b, devs[0]))\n"
            "    return out\n"
        )
        bad = lint_lib(swap_loop, ["R5"],
                       rel="raft_tpu/serving/placement.py")
        assert rules_fired(bad) == {"R5"}

    def test_r5_covers_fleet(self):
        """PR 20: graftroute modules are serving-hot — a host fetch
        inside a fleet search path (or a traced body) must fire R5
        exactly as it would in raft_tpu/serving/."""
        fleet_sync = (
            "import numpy as np\n"
            "\n"
            "\n"
            "def search_fanout(handles):\n"
            "    return [np.asarray(h.result()) for h in handles]\n"
        )
        bad = lint_lib(fleet_sync, ["R5"],
                       rel="raft_tpu/fleet/router.py")
        assert rules_fired(bad) == {"R5"}
        # the router's actual discipline: no search*-named host
        # functions, merges stay in jnp
        ok = (
            "import jax.numpy as jnp\n"
            "\n"
            "\n"
            "def merge_legs(parts, k):\n"
            "    return jnp.concatenate(parts, axis=1)[:, :k]\n"
        )
        assert lint_lib(ok, ["R5"],
                        rel="raft_tpu/fleet/router.py").ok

    def test_r7_covers_fleet(self):
        """PR 20: the router measures table age — only against the
        injected clock, same discipline as the serving frontend."""
        table_age = (
            "import time\n"
            "\n"
            "\n"
            "def table_age(applied_at):\n"
            "    return time.monotonic() - applied_at\n"
        )
        bad = lint_lib(table_age, ["R7"],
                       rel="raft_tpu/fleet/router.py")
        assert rules_fired(bad) == {"R7"}
        ok = (
            "def table_age(clock, applied_at):\n"
            "    return clock.now() - applied_at\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/fleet/router.py").ok

    def test_r7_covers_placement(self):
        epoch_clock = (
            "import time\n"
            "\n"
            "\n"
            "def epoch_due(last):\n"
            "    return time.monotonic() - last > 60.0\n"
        )
        bad = lint_lib(epoch_clock, ["R7"],
                       rel="raft_tpu/serving/placement.py")
        assert rules_fired(bad) == {"R7"}
        # the conforming discipline the module actually uses
        ok = (
            "def epoch_due(clock, last):\n"
            "    return clock.now() - last > 60.0\n"
        )
        assert lint_lib(ok, ["R5", "R7"],
                        rel="raft_tpu/serving/placement.py").ok


# graftragged scope proof: the MESH ragged plan keys fold mesh devices
# and params-class tuples into RETURN position of ragged_key — R1's
# key discipline covers that construction (the shipped executor's
# ragged_key/coalesce_key lint clean, suppression snapshot unchanged).

R1_MESH_RAGGED_KEY_VIOLATING = '''\
def ragged_key(self, index, k, params=None, **kw):
    return ("dist_ivf_flat_ragged",
            [d.id for d in index.mesh_devices],
            float(index.probe_budget),
            {"wire": kw.get("wire_dtype")})
'''
R1_MESH_RAGGED_KEY_CONFORMING = '''\
def ragged_key(self, index, k, params=None, **kw):
    return ("dist_ivf_flat_ragged", index.mesh_key,
            tuple(sorted((n, str(v)) for n, v in kw.items())),
            k)
'''


class TestMeshRaggedKeyProofs:
    """graftragged satellite: R1 key discipline reaches the mesh
    ragged plan keys — device-id lists, runtime-data scalars, and
    bare dict displays in a key-returning function's RETURN are
    findings; the tuple-wrapped mesh-device + params-class + wire-kw
    construction conforms."""

    def test_mesh_ragged_key_violating(self):
        bad = lint_lib(R1_MESH_RAGGED_KEY_VIOLATING, ["R1"],
                       rel="raft_tpu/core/executor.py")
        assert rules_fired(bad) == {"R1"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "unhashable list" in msgs
        assert "float() of runtime data" in msgs
        assert "unhashable dict" in msgs

    def test_mesh_ragged_key_conforming(self):
        assert lint_lib(R1_MESH_RAGGED_KEY_CONFORMING, ["R1"],
                        rel="raft_tpu/core/executor.py").ok


# ---------------------------------------------------------------------------
# PR 19: graftlint v3 — R8 lock discipline, R2v2 interprocedural
# donation escape, R9 metric-inventory conformance, the program graph
# they stand on, and the incremental cache
# ---------------------------------------------------------------------------

R8_VIOLATING = '''\
import threading


class Depot:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def bump(self):
        with self._lock:
            self._n += 1

    def peek(self):
        return self._n
'''
R8_CONFORMING = '''\
import threading


class Depot:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def bump(self):
        with self._lock:
            self._n += 1

    def peek(self):
        with self._lock:
            return self._n
'''
R8_HELPER_CONFORMING = '''\
import threading


class Depot:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def bump(self):
        with self._lock:
            self._bump_locked()

    def _bump_locked(self):
        self._n += 1
'''
R8_HELPER_ESCAPE_VIOLATING = R8_HELPER_CONFORMING + '''\

    def leak(self):
        self._bump_locked()
'''
R8_CALLBACK_VIOLATING = '''\
import threading


class Poller:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def arm(self, loop):
        loop.call(self._on_tick)

    def _on_tick(self):
        self._n += 1
'''
R8_UNKNOWN_LOCK = '''\
import threading


class Depot:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _missing
'''
R8_GLOBAL_VIOLATING = '''\
import threading

_lock = threading.Lock()
_total = 0  # guarded-by: _lock


def bump(n):
    global _total
    with _lock:
        _total += n


def peek():
    return _total
'''
R8_CYCLE_VIOLATING = '''\
import threading

_a = threading.Lock()
_b = threading.Lock()


def left():
    with _a:
        with _b:
            pass


def right():
    with _b:
        with _a:
            pass
'''
R8_CYCLE_CONFORMING = '''\
import threading

_a = threading.Lock()
_b = threading.Lock()


def left():
    with _a:
        with _b:
            pass


def right():
    with _a:
        with _b:
            pass
'''
R8_SELF_DEADLOCK_VIOLATING = '''\
import threading

_m = threading.Lock()


def outer():
    with _m:
        inner()


def inner():
    with _m:
        pass
'''
R8_SELF_DEADLOCK_CONFORMING = \
    R8_SELF_DEADLOCK_VIOLATING.replace("threading.Lock()",
                                       "threading.RLock()")


class TestLockDiscipline:
    """R8 fixture corpus: guarded-by accesses checked lexically and
    through private-helper call sites, annotation hygiene, and the
    static lock graph's cycle / self-deadlock findings."""

    def test_unguarded_read_fires(self):
        bad = lint_lib(R8_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        msg = bad.findings[0].message
        assert "read of 'self._n'" in msg and "Depot.peek" in msg, msg
        assert lint_lib(R8_CONFORMING, ["R8"]).ok

    def test_private_helper_inherits_callers_lock(self):
        assert lint_lib(R8_HELPER_CONFORMING, ["R8"]).ok
        # one unlocked call site and the helper's guarantee is gone
        bad = lint_lib(R8_HELPER_ESCAPE_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        assert "_bump_locked" in bad.findings[0].message

    def test_callback_reference_never_inherits(self):
        bad = lint_lib(R8_CALLBACK_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        assert "_on_tick" in bad.findings[0].message

    def test_annotation_must_name_a_real_lock(self):
        bad = lint_lib(R8_UNKNOWN_LOCK, ["R8"])
        assert rules_fired(bad) == {"R8"}
        assert "no lock of that name exists" in bad.findings[0].message

    def test_module_globals_are_covered(self):
        bad = lint_lib(R8_GLOBAL_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        assert "read of '_total'" in bad.findings[0].message

    def test_lock_order_cycle(self):
        bad = lint_lib(R8_CYCLE_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        msgs = " ".join(f.message for f in bad.findings)
        assert "lock-order cycle" in msgs, msgs
        assert "_a" in msgs and "_b" in msgs
        assert lint_lib(R8_CYCLE_CONFORMING, ["R8"]).ok

    def test_interprocedural_self_deadlock(self):
        bad = lint_lib(R8_SELF_DEADLOCK_VIOLATING, ["R8"])
        assert rules_fired(bad) == {"R8"}
        assert "self-deadlock" in bad.findings[0].message
        assert lint_lib(R8_SELF_DEADLOCK_CONFORMING, ["R8"]).ok

    def test_lockgraph_artifact_shape(self):
        from raft_tpu.analysis.core import Project
        from raft_tpu.analysis.rules_locks import build_lock_graph

        project = Project.from_texts(
            {"raft_tpu/ops/sample.py": R8_CYCLE_VIOLATING})
        d = build_lock_graph(project).to_dict()
        assert sorted(d) == ["cycles", "edges", "locks",
                             "self_deadlocks"]
        assert len(d["locks"]) == 2
        assert d["cycles"], d
        assert not d["self_deadlocks"]


R2_INTERPROC_VIOLATING = '''\
import jax


def _step_fn(state):
    return state


def _advance(state):
    step = jax.jit(_step_fn, donate_argnums=(0,))
    return step(state)


def serve(state):
    out = _advance(state)
    return out + state
'''
R2_INTERPROC_CONFORMING = '''\
import jax


def _step_fn(state):
    return state


def _advance(state):
    step = jax.jit(_step_fn, donate_argnums=(0,))
    return step(state)


def serve(state):
    state = _advance(state)
    return state
'''
R2_FIELD_ESCAPE_VIOLATING = '''\
import jax


def _step_fn(plane):
    return plane


def _consume(entry):
    step = jax.jit(_step_fn, donate_argnums=(0,))
    return step(entry.plane)


def refresh(entry):
    out = _consume(entry)
    return out + entry.plane
'''
R2_METHOD_ESCAPE_VIOLATING = '''\
import jax


def _step_fn(state):
    return state


class Entry:
    def claim(self):
        step = jax.jit(_step_fn, donate_argnums=(0,))
        return step(self.state)


def roll():
    entry = Entry()
    out = entry.claim()
    return out + entry.state
'''


class TestDonationEscape:
    """R2v2 fixture corpus: donation summaries flow across function
    boundaries — a helper that donates its argument taints every
    caller, fields included, while result-threading stays blessed."""

    def test_escape_through_helper(self):
        bad = lint_lib(R2_INTERPROC_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}
        msg = bad.findings[0].message
        assert "donation escaping through '_advance'" in msg, msg
        assert lint_lib(R2_INTERPROC_CONFORMING, ["R2"]).ok

    def test_field_path_escape(self):
        bad = lint_lib(R2_FIELD_ESCAPE_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}
        assert "'entry.plane'" in bad.findings[0].message
        # an un-donated sibling field stays readable
        ok = R2_FIELD_ESCAPE_VIOLATING.replace(
            "return out + entry.plane", "return out + entry.meta")
        assert lint_lib(ok, ["R2"]).ok

    def test_method_receiver_escape(self):
        bad = lint_lib(R2_METHOD_ESCAPE_VIOLATING, ["R2"])
        assert rules_fired(bad) == {"R2"}
        assert "'entry.state'" in bad.findings[0].message


R9_LIB = '''\
from raft_tpu.core import tracing


def record(n, split):
    tracing.inc_counter("serving.sample.calls", n)
    tracing.inc_counter(f"serving.sample.{split}.rows", n)
    tracing.set_gauge("serving.sample.depth", n)
'''
R9_ARCH_OK = (
    "## Metric inventory\n"
    "\n"
    "| name | type | meaning |\n"
    "| --- | --- | --- |\n"
    "| `serving.sample.calls` | counter | total calls |\n"
    "| `serving.sample.<split>.rows` | counter | rows per split |\n"
    "| `serving.sample.depth` | gauge | queue depth |\n"
)
R9_ARCH_MISSING_GAUGE = R9_ARCH_OK.replace(
    "| `serving.sample.depth` | gauge | queue depth |\n", "")
R9_FLOORS_OK = (
    "SNAPSHOT_FLOORS = {\n"
    '    "serving.sample.calls": 10,\n'
    "}\n"
)
R9_FLOORS_DEAD = (
    "SNAPSHOT_FLOORS = {\n"
    '    "serving.sample.calls": 10,\n'
    '    "serving.sample.ghost": 1,\n'
    "}\n"
)
R9_EXPORTER_OK = (
    "_HELP_PREFIXES = (\n"
    '    ("serving.sample", "sample family"),\n'
    ")\n"
)
R9_EXPORTER_DEAD = (
    "_HELP_PREFIXES = (\n"
    '    ("serving.sample", "sample family"),\n'
    '    ("serving.ghostly", "nothing registers this"),\n'
    ")\n"
)


class TestMetricInventory:
    """R9 fixture corpus: the registered-pattern inventory against the
    ARCHITECTURE.md tables, SNAPSHOT_FLOORS, and _HELP_PREFIXES — each
    drift direction is one finding, and the rule is quiet when a
    fixture project supplies no aux evidence."""

    def test_documented_inventory_conforms(self):
        rep = lint_texts({"raft_tpu/serving/sample.py": R9_LIB},
                         rules=["R9"],
                         aux={"ARCHITECTURE.md": R9_ARCH_OK})
        assert rep.ok, [f.render() for f in rep.findings]

    def test_undocumented_gauge_fires(self):
        rep = lint_texts({"raft_tpu/serving/sample.py": R9_LIB},
                         rules=["R9"],
                         aux={"ARCHITECTURE.md": R9_ARCH_MISSING_GAUGE})
        assert rules_fired(rep) == {"R9"}
        msg = rep.findings[0].message
        assert "gauge 'serving.sample.depth'" in msg, msg
        assert "ARCHITECTURE.md" in msg

    def test_dead_floor_fires(self):
        rep = lint_texts({"raft_tpu/serving/sample.py": R9_LIB},
                         rules=["R9"],
                         aux={"ARCHITECTURE.md": R9_ARCH_OK,
                              "ci/bench_compare.py": R9_FLOORS_DEAD})
        assert rules_fired(rep) == {"R9"}
        msg = rep.findings[0].message
        assert "serving.sample.ghost" in msg and "floor" in msg, msg
        assert rep.findings[0].path == "ci/bench_compare.py"
        rep = lint_texts({"raft_tpu/serving/sample.py": R9_LIB},
                         rules=["R9"],
                         aux={"ARCHITECTURE.md": R9_ARCH_OK,
                              "ci/bench_compare.py": R9_FLOORS_OK})
        assert rep.ok

    def test_dead_help_prefix_fires(self):
        texts = {"raft_tpu/serving/sample.py": R9_LIB,
                 "raft_tpu/serving/exporter.py": R9_EXPORTER_DEAD}
        rep = lint_texts(texts, rules=["R9"],
                         aux={"ARCHITECTURE.md": R9_ARCH_OK})
        assert rules_fired(rep) == {"R9"}
        assert "serving.ghostly" in rep.findings[0].message
        texts["raft_tpu/serving/exporter.py"] = R9_EXPORTER_OK
        assert lint_texts(texts, rules=["R9"],
                          aux={"ARCHITECTURE.md": R9_ARCH_OK}).ok

    def test_quiet_without_aux(self):
        assert lint_texts({"raft_tpu/serving/sample.py": R9_LIB},
                          rules=["R9"]).ok


class TestProgGraph:
    """The cross-module program graph R8/R9/R2v2 stand on."""

    def test_guarded_fields_and_lock_kinds(self):
        from raft_tpu.analysis import proggraph
        from raft_tpu.analysis.core import Project

        src = (
            "import threading\n"
            "import dataclasses\n"
            "from dataclasses import field\n"
            "\n"
            "\n"
            "@dataclasses.dataclass\n"
            "class Plane:\n"
            "    rows: int = 0  # guarded-by: _swap_lock\n"
            "    _swap_lock: object = field(\n"
            "        default_factory=threading.Lock)\n"
            "\n"
            "\n"
            "class Depot:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
        )
        project = Project.from_texts({"raft_tpu/core/sample.py": src})
        graph = proggraph.get_graph(project)
        mod = graph.modules["raft_tpu/core/sample.py"]
        plane = mod.classes["Plane"]
        assert plane.fields["rows"].guarded_by == "_swap_lock"
        assert plane.fields["_swap_lock"].is_lock
        depot = mod.classes["Depot"]
        assert depot.fields["_n"].guarded_by == "_lock"
        assert depot.fields["_lock"].is_lock

    def test_cross_module_call_resolution(self):
        from raft_tpu.analysis import proggraph
        from raft_tpu.analysis.core import Project

        project = Project.from_texts({
            "raft_tpu/core/util.py": (
                "def helper(x):\n"
                "    return x\n"),
            "raft_tpu/core/main.py": (
                "from raft_tpu.core.util import helper\n"
                "\n"
                "\n"
                "def caller(x):\n"
                "    return helper(x)\n")})
        graph = proggraph.get_graph(project)
        fn = graph.modules["raft_tpu/core/main.py"].functions["caller"]
        callees = [c.name for c, _call in graph.callees(fn)]
        assert callees == ["helper"]


class TestLintCache:
    """The incremental content-hash cache: per-file keys for
    file-scope rules, one project digest for program-scope rules, and
    version-stamped invalidation."""

    TEXTS = {"raft_tpu/ops/a.py": "x = 1\n",
             "raft_tpu/ops/b.py": "y = 2\n"}

    def _run(self, path, texts, rules, version="v1"):
        from raft_tpu.analysis import LintCache
        from raft_tpu.analysis.core import Project, run

        cache = LintCache(path, version)
        rep = run(Project.from_texts(texts), rules=rules, cache=cache)
        cache.save()
        return rep

    def test_second_run_is_all_hits(self, tmp_path):
        path = tmp_path / "cache.json"
        r1 = self._run(path, self.TEXTS, ["R0"])
        assert r1.cache_misses == 2 and r1.cache_hits == 0
        r2 = self._run(path, self.TEXTS, ["R0"])
        assert r2.cache_hits == 2 and r2.cache_misses == 0
        assert r2.ok == r1.ok

    def test_edit_invalidates_only_that_file(self, tmp_path):
        path = tmp_path / "cache.json"
        self._run(path, self.TEXTS, ["R0"])
        edited = dict(self.TEXTS)
        edited["raft_tpu/ops/b.py"] = "y = 3\n"
        r = self._run(path, edited, ["R0"])
        assert r.cache_hits == 1 and r.cache_misses == 1

    def test_program_scope_keys_on_project_digest(self, tmp_path):
        path = tmp_path / "cache.json"
        r1 = self._run(path, self.TEXTS, ["R8"])
        assert (r1.cache_hits, r1.cache_misses) == (0, 1)
        r2 = self._run(path, self.TEXTS, ["R8"])
        assert (r2.cache_hits, r2.cache_misses) == (1, 0)
        # ANY file edit re-runs a whole-program rule
        edited = dict(self.TEXTS)
        edited["raft_tpu/ops/b.py"] = "y = 3\n"
        r3 = self._run(path, edited, ["R8"])
        assert (r3.cache_hits, r3.cache_misses) == (0, 1)

    def test_ruleset_version_change_invalidates(self, tmp_path):
        path = tmp_path / "cache.json"
        self._run(path, self.TEXTS, ["R0"])
        r = self._run(path, self.TEXTS, ["R0"], version="v2")
        assert r.cache_hits == 0 and r.cache_misses == 2

    def test_cached_findings_match_fresh(self, tmp_path):
        path = tmp_path / "cache.json"
        texts = {"raft_tpu/ops/a.py": R0_VIOLATING}
        r1 = self._run(path, texts, ["R0"])
        r2 = self._run(path, texts, ["R0"])
        assert r2.cache_hits > 0
        assert ([f.render() for f in r1.findings]
                == [f.render() for f in r2.findings])
