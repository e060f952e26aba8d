"""True multi-process distributed validation — the reference validates
MNMG logic with real NCCL over local worker processes
(raft_dask/test/test_comms.py LocalCUDACluster); the analog here is
``jax.distributed.initialize`` over local CPU processes: a 2-process
clique forms a global mesh and runs the comms collectives through the
same ``raft_tpu.comms`` code path multi-host TPU uses over DCN."""

import os
import pathlib
import socket
import subprocess
import sys
import textwrap

import pytest

# workers do `sys.path.insert(0, os.getcwd())`, so launch them with the
# repo root as cwd wherever this checkout lives
REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])

_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    sys.path.insert(0, os.getcwd())   # launched with cwd = repo root
    from raft_tpu.comms import Comms, bootstrap
    from raft_tpu.comms.comms import allreduce, rank
    from jax.sharding import PartitionSpec as P
    import jax.numpy as jnp

    bootstrap.initialize(f"127.0.0.1:{port}", nproc, pid)
    assert len(jax.devices()) == nproc, jax.devices()
    assert jax.process_count() == nproc

    comms = Comms(bootstrap.make_mesh(), "data")
    assert comms.process_rank == pid

    x = jax.device_put(
        jnp.arange(nproc * 4, dtype=jnp.float32).reshape(nproc, 4),
        comms.row_sharded(),
    )

    def body(xl):
        return allreduce(xl, axis="data") + 0.0 * rank("data")

    out = comms.run(body, x, in_specs=(P("data", None),),
                    out_specs=P("data", None), check_vma=False)
    local = out.addressable_shards[0].data
    assert float(local.sum()) == float(
        jnp.arange(nproc * 4, dtype=jnp.float32).sum()
    ), local
    print(f"proc {pid} OK", flush=True)
""")


_WORKER_STACK = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    # 4 virtual devices per process -> 8-device global mesh
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, nproc, port, ckpt = (int(sys.argv[1]), int(sys.argv[2]),
                              sys.argv[3], sys.argv[4])
    sys.path.insert(0, os.getcwd())
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from raft_tpu.comms import Comms, bootstrap
    from raft_tpu.comms.comms import allreduce
    from raft_tpu.distributed import checkpoint as ckpt_mod
    from raft_tpu.distributed import ivf as dist_ivf
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.neighbors.ivf_flat import (IvfFlatIndexParams,
                                             IvfFlatSearchParams)
    from raft_tpu.neighbors.ivf_pq import IvfPqIndexParams, IvfPqSearchParams

    bootstrap.initialize(f"127.0.0.1:{port}", nproc, pid)
    assert len(jax.devices()) == nproc * 4, jax.devices()
    comms = Comms(bootstrap.make_mesh(), "data")

    def fetch(a):
        return np.asarray(a.addressable_shards[0].data)

    def sync():
        # a fetched collective is a cross-process barrier: it cannot
        # complete until every process has reached (and enqueued) it
        out = comms.run(lambda x: allreduce(x, axis="data"),
                        jax.device_put(jnp.ones((comms.size, 1)),
                                       comms.row_sharded()),
                        in_specs=(P("data", None),),
                        out_specs=P("data", None), check_vma=False)
        fetch(out)

    # deterministic data, identical in both processes
    rng = np.random.default_rng(123)
    x = rng.standard_normal((2000, 32)).astype(np.float32)
    q = rng.standard_normal((16, 32)).astype(np.float32)

    # ---- IVF-Flat: distributed build + search vs single-chip parity
    fparams = IvfFlatIndexParams(n_lists=16, kmeans_n_iters=8)
    fsearch = IvfFlatSearchParams(n_probes=8)
    dist_index = dist_ivf.build(None, comms, fparams, x)
    dd, di = dist_ivf.search(None, fsearch, dist_index, q, 10,
                             probe_mode="global")
    dd, di = fetch(dd), fetch(di)

    ref_index = ivf_flat.build(None, fparams, x)
    rd, ri = ivf_flat.search(None, fsearch, ref_index, q, 10)
    np.testing.assert_array_equal(di, np.asarray(ri))
    np.testing.assert_allclose(dd, np.asarray(rd), rtol=1e-5, atol=1e-5)
    print(f"proc {pid} flat parity OK", flush=True)

    # ---- checkpoint: per-process save -> barrier -> reshard onto a
    #      4-device sub-mesh (2 devices from each process)
    ckpt_mod.save_flat_multihost(dist_index, ckpt)
    sync()
    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    half = [d for ds in by_proc.values()
            for d in sorted(ds, key=lambda d: d.id)[:2]]
    comms4 = Comms(bootstrap.make_mesh(devices=half), "data")
    loaded = ckpt_mod.load_flat_multihost(None, comms4, ckpt)
    assert loaded.centers.sharding.num_devices == 4
    ld, li = dist_ivf.search(None, fsearch, loaded, q, 10,
                             probe_mode="global")
    np.testing.assert_array_equal(fetch(li), di)
    np.testing.assert_allclose(fetch(ld), dd, rtol=1e-5, atol=1e-5)
    print(f"proc {pid} reshard OK", flush=True)

    # ---- IVF-PQ: distributed build + search + multihost round-trip
    pparams = IvfPqIndexParams(n_lists=16, pq_dim=8, pq_bits=8,
                               kmeans_n_iters=8)
    psearch = IvfPqSearchParams(n_probes=16)
    pq_dist = dist_ivf.build_pq(None, comms, pparams, x)
    pd, pi = dist_ivf.search_pq(None, psearch, pq_dist, q, 10,
                                probe_mode="global")
    pd, pi = fetch(pd), fetch(pi)
    pq_ref = ivf_pq.build(None, pparams, x)
    prd, pri = ivf_pq.search(None, psearch, pq_ref, q, 10)
    np.testing.assert_array_equal(pi, np.asarray(pri))

    pq_ckpt = ckpt + "_pq"
    ckpt_mod.save_pq_multihost(pq_dist, pq_ckpt)
    sync()
    pq_loaded = ckpt_mod.load_pq_multihost(None, comms4, pq_ckpt)
    p2d, p2i = dist_ivf.search_pq(None, psearch, pq_loaded, q, 10,
                                  probe_mode="global")
    np.testing.assert_array_equal(fetch(p2i), pi)
    np.testing.assert_allclose(fetch(p2d), pd, rtol=1e-5, atol=1e-5)
    print(f"proc {pid} OK", flush=True)
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# capability probe — some jaxlib/backend combinations accept
# jax.distributed.initialize but reject actually *running* a
# cross-process computation (jaxlib 0.4.37 CPU: "Multiprocess
# computations aren't implemented on the CPU backend"; see the
# ROADMAP "Known-environmental" note). That is an environment limit,
# not a repo bug, so these tests skip instead of failing. Re-check
# when the container's jax moves.
_CAPABILITY_ERRORS = (
    "Multiprocess computations aren't implemented",
    "non-addressable device",
)

_PROBE = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    sys.path.insert(0, os.getcwd())
    from raft_tpu.comms import Comms, bootstrap
    from raft_tpu.comms.comms import allreduce
    from jax.sharding import PartitionSpec as P
    import jax.numpy as jnp

    bootstrap.initialize(f"127.0.0.1:{port}", nproc, pid)
    comms = Comms(bootstrap.make_mesh(), "data")
    out = comms.run(lambda v: allreduce(v, axis="data"),
                    jax.device_put(jnp.ones((nproc, 1)),
                                   comms.row_sharded()),
                    in_specs=(P("data", None),),
                    out_specs=P("data", None), check_vma=False)
    assert float(out.addressable_shards[0].data.sum()) == nproc
    print("probe OK", flush=True)
""")

_probe_result = None


def _multiprocess_capability(tmp_path_factory) -> tuple:
    """(supported, detail) — cached for the session; one minimal
    2-process allreduce tells us whether the backend can run
    cross-process computations at all."""
    global _probe_result
    if _probe_result is not None:
        return _probe_result
    worker = tmp_path_factory.mktemp("mp_probe") / "probe.py"
    worker.write_text(_PROBE)
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=REPO_ROOT,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out = b"probe timed out"
        outs.append(out.decode())
    ok = all(p.returncode == 0 and "probe OK" in o
             for p, o in zip(procs, outs))
    if ok:
        _probe_result = (True, "")
    else:
        combined = "\n".join(outs)
        known = [e for e in _CAPABILITY_ERRORS if e in combined]
        if known:
            _probe_result = (False, known[0])
        else:
            # an unknown failure is a real bug — do NOT mask it
            _probe_result = (True, "")
    return _probe_result


@pytest.fixture()
def multiprocess_backend(tmp_path_factory):
    supported, detail = _multiprocess_capability(tmp_path_factory)
    if not supported:
        pytest.skip(
            "backend rejects cross-process computations "
            f"({detail!r}) — known-environmental, see ROADMAP.md")


def test_two_process_clique(tmp_path, multiprocess_backend):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=REPO_ROOT,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process clique timed out")
        outs.append(out.decode())
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} OK" in out


def test_two_process_distributed_stack(tmp_path, multiprocess_backend):
    """VERDICT r2 #5: the full distributed stack across process
    boundaries — dist IVF-Flat/PQ build + search (bit-parity with the
    single-chip result), per-process checkpoint save, and a reshard
    8 devices -> 4 (a 2x2 sub-mesh spanning both processes) on load."""
    worker = tmp_path / "worker_stack.py"
    worker.write_text(_WORKER_STACK)
    ckpt = tmp_path / "ckpt_flat"
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), "2", str(port),
             str(ckpt)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=REPO_ROOT,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=480)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process distributed stack timed out")
        outs.append(out.decode())
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} flat parity OK" in out
        assert f"proc {pid} reshard OK" in out
        assert f"proc {pid} OK" in out
