"""FLOP, byte and peak functions against counts made by hand."""
import pytest

import flops
import peaks
import weights
from conftest import _load

GRANITE = _load("configs", "granite-8b")
CHAIN = _load("configs", "granite-8b-offload")


def test_granite_layer_params_by_hand():
    # wq 4096*4096 + wk, wv 4096*1024 each + wo 4096*4096 + 3 * 4096*14336
    hand = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert flops.granite_layer_params(GRANITE) == hand == 218_103_808


def test_granite_weight_bytes_by_hand():
    # 8 layers of matmuls and two norm scales, the final norm, one tied
    # 49152 x 4096 embedding; bf16
    hand = 2 * (8 * (218_103_808 + 2 * 4096) + 4096 + 49152 * 4096)
    assert flops.granite_weight_bytes(GRANITE, 8) == hand == 3_892_453_376


def test_kv_bytes_per_token_by_hand():
    # K and V, 8 layers, 8 heads of 128, bf16: 32 KiB
    assert flops.kv_bytes_per_token(GRANITE, 8) == 2 * 8 * 8 * 128 * 2 == 32768


def test_decode_step_bytes_counts_positions_up_to_pos():
    w = flops.granite_weight_bytes(GRANITE, 8)
    # lanes at positions 0 and 9: read 1 and 10 positions, write 1 each
    assert flops.decode_step_bytes(GRANITE, 8, [0, 9]) == w + 32768 * (1 + 10 + 2)


def test_token_flops_by_hand():
    # 2 per matmul parameter (8 layers and the 4096 x 49152 head), plus
    # QK^T and PV over pos + 1 = 100 positions: 8 * 2 * 2 * 32 * 128 * 100
    hand = 2 * (8 * 218_103_808 + 4096 * 49152) + 8 * 4 * 32 * 128 * 100
    assert flops.token_flops(GRANITE, 8, 99) == hand


def test_every_chain_stage_shape_and_its_counts():
    shapes = [s for _n, s in weights.chain_shapes(CHAIN)]
    assert len(shapes) == 144
    assert set(shapes) == {(4096, 4096), (4096, 14336), (14336, 4096)}
    total = sum(k * n * 2 for k, n in shapes)
    assert total == 36 * 2 * (2 * 4096 * 4096 + 2 * 4096 * 14336) == 10_871_635_968
    m = 4096
    for k, n in set(shapes):
        assert flops.matmul_flops(m, k, n) == 2 * m * k * n
        assert flops.matmul_bytes(m, k, n) == 2 * (m * k + k * n + m * n)
    # a 4096^3 product: 137.4 GFLOP, 100.7 MB; compute-bound on v5e
    v5e = peaks.peaks_for("TPU v5 lite")
    t = flops.roofline_seconds(flops.matmul_flops(4096, 4096, 4096),
                               flops.matmul_bytes(4096, 4096, 4096), v5e)
    assert t == pytest.approx(137_438_953_472 / 197e12)
    # one pass: 44.5 TFLOP
    assert sum(flops.matmul_flops(m, k, n) for k, n in shapes) == pytest.approx(
        44.53e12, rel=1e-3)


def test_peaks_table_has_v5e_with_its_source():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in the peaks table"):
        peaks.peaks_for("TPU v99 imaginary")
