"""CPU rehearsal of ``chip_smoke.py``: its phases run on tiny generated
targets (Pallas kernels in interpret mode) and must agree with the numpy
reference, and ``main`` must refuse any device that is not a TPU."""

from __future__ import annotations

import pytest

import chip_smoke
from repro.core import EngineConfig

TINY_ENGINE = EngineConfig(n_workers=4, expand_width=4)


def test_dense_phases_match_reference():
    """Phases A (jnp) and B (fused kernel) through the service: counts,
    states and every mapping equal the reference, and A steals."""
    tgt, pats = chip_smoke.generate(chip_smoke.Workload(
        n_t=96, m=300, n_labels=4, label_dist="normal",
        pattern_edges=(4, 8), n_queries=4), seed=0)
    phases = chip_smoke.dense_phases(tgt, pats, TINY_ENGINE, clients=2,
                                     max_lanes=2, timeout=300.0,
                                     require_steals=True)
    assert [p["phase"] for p in phases] == ["A", "B"]
    a, b = phases
    assert a["queries"] == b["queries"] == 4
    assert (a["matches"], a["states"]) == (b["matches"], b["states"])
    assert a["states"] > 0 and a["max_steals"] > 0


def test_sparse_phase_matches_reference():
    """Phase C: a CSR-only index, kernels for the AC sweep and the walk."""
    tgt, pats = chip_smoke.generate(chip_smoke.Workload(
        n_t=200, m=400, n_labels=3, label_dist="uniform",
        pattern_edges=(4, 8), n_queries=4), seed=1)
    c = chip_smoke.sparse_phase(tgt, pats, TINY_ENGINE, clients=2,
                                max_lanes=1, timeout=300.0)
    assert c["queries"] == 4 and c["states"] > 0


def test_mesh_phase_matches_reference():
    """The ``--chips`` path on however many devices this host has."""
    import jax

    tgt, pats = chip_smoke.generate(chip_smoke.Workload(
        n_t=64, m=200, n_labels=4, label_dist="normal",
        pattern_edges=(4,), n_queries=2), seed=2)
    out = chip_smoke.mesh_phase(tgt, pats, TINY_ENGINE, len(jax.devices()))
    assert len(out["steals_per_device"]) == len(jax.devices())


def test_check_rejects_a_wrong_mapping():
    tgt, pats = chip_smoke.generate(chip_smoke.Workload(
        n_t=48, m=150, n_labels=3, label_dist="normal",
        pattern_edges=(4,), n_queries=1), seed=3)
    (ref, plan), = chip_smoke.reference(tgt, pats, "ri-ds-si-fc")
    assert ref.matches > 0
    wrong = [tuple(reversed(m)) for m in ref.mappings[:-1]] + [ref.mappings[-1]]
    chip_smoke.check("q", ref.matches, ref.states, ref.mappings, plan, ref, plan)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check("q", ref.matches, ref.states, wrong[:-1], plan, ref, plan)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check("q", ref.matches, ref.states + 1, ref.mappings, plan,
                         ref, plan)


def test_main_refuses_a_device_that_is_not_a_tpu(capsys):
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
