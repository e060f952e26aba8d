"""CPU rehearsal of ``chip_smoke.py`` at a tiny size: every single-chip
phase (the IVF and CAGRA kernels in interpret mode) and the ``--chips
4`` mesh comparison on four virtual CPU devices. The script's checks
run unchanged; only the sizes shrink, and the chip-only checks (kernel
engine, ``tpu_custom_call``) are left to the chip."""

import jax
import pytest

import chip_smoke

N, DIM = 4096, 128


@pytest.fixture(scope="module")
def data():
    return chip_smoke.make_data(N, DIM, 20, seed=0, n_clusters=64)


def test_single_chip_phases(data, capsys):
    x, q = data
    recs = chip_smoke.run_single_chip(
        x, q, n_lists=16, pq_dim=64, n_probes=8, cagra_n=2048,
        cagra_degree=32, cagra_idegree=64, require_kernels=False)
    assert [r["phase"] for r in recs] == [
        "bf_f32", "bf_bf16", "ivf_flat", "ivf_pq", "ivf_bq", "cagra"]
    for r in recs:
        assert r["compiles_after_warmup"] == 0
        assert r["requests"] == 2
        assert r["recall"] >= chip_smoke.FLOORS[r["phase"]]
    # the IVF and CAGRA kernels ran (interpret mode) and matched xla
    engines = {r["phase"]: r["engine"] for r in recs}
    assert engines["ivf_flat"] == engines["ivf_bq"] == "pallas"
    assert engines["cagra"] == "pallas"
    assert all(r.get("xla_id_mismatches", 0) == 0 for r in recs)
    assert '"phase": "cagra"' in capsys.readouterr().out


def test_mesh_phase(data):
    x, q = data
    rec = chip_smoke.run_mesh(x, q, jax.devices()[:4], n_lists=16,
                              n_probes=8)
    assert rec["ids_equal"] and rec["distances_equal"]
    assert rec["lists_per_device"] == {str(d.id): 4
                                       for d in jax.devices()[:4]}


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
