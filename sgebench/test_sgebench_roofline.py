"""Peaks and the kernels' least work, at phase C's shapes (pdbsv1's
n_t = 33,067, w = 1,034 words, 64 workers x 64 lanes)."""

from __future__ import annotations

import pytest

from sgebench import roofline


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.least_seconds({"bytes": 1, "ops": 1}, "cpu")


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


def test_csr_extend_work_at_phase_c():
    # 4,096 lanes with 8 parent slots each, on 33,067 nodes whose planes
    # hold 264,536 words (mean segment 8 words).  Per lane: 16 words of
    # segment bounds, 8 segment words, 2 rows read and 2 written of 1,034
    # words and a 4-word meta row: 4,096 * 4,164 words of 4 bytes
    work = roofline.csr_extend_work(b=4096, w=1034, mp=8, nnz_plane=264_536,
                                    n_t=33067)
    assert work["bytes"] == pytest.approx(4 * 4096 * (16 + 8 + 4136 + 4))
    assert work["bytes"] == pytest.approx(68_222_976)
    assert work["ops"] == pytest.approx(4096 * (4136 + 8))
    t = roofline.least_seconds(work, "TPU v5 lite")
    assert t == pytest.approx(68_222_976 / 819e9)  # bandwidth-bound
    assert 83e-6 < t < 84e-6


def test_csr_arc_sweep_work_at_phase_c():
    # 2 planes' start and length tables (2 * 2 * 33,067 words), and for
    # each of 32 arcs every segment word of its plane (264,536), a mask
    # and a packed output row of 1,034 words
    work = roofline.csr_arc_sweep_work(n_arcs=32, n_planes=2, n_t=33067,
                                       w=1034, nnz_plane=264_536)
    assert work["bytes"] == 4 * (132_268 + 32 * (264_536 + 2_068))
    assert work["bytes"] == 34_654_384
    assert work["ops"] == 32 * 264_536
    assert roofline.least_seconds(work, "TPU v5 lite") == pytest.approx(
        34_654_384 / 819e9)


@pytest.mark.parametrize("mp", [1, 2, 8])
def test_extend_work_grows_with_parent_slots(mp):
    base = roofline.csr_extend_work(b=64, w=128, mp=0, nnz_plane=8000,
                                    n_t=1000)
    work = roofline.csr_extend_work(b=64, w=128, mp=mp, nnz_plane=8000,
                                    n_t=1000)
    assert work["bytes"] - base["bytes"] == 4 * 64 * 2 * mp
