"""True-shape operation and byte counts, checked against hand arithmetic."""
import pytest

import work

OURO = work.BlockShapes(2048, 16, 5632, 49152, 2, {"qkv": 4, "o": 8, "up": 8, "down": 4})
MINICPM = work.BlockShapes(2304, 36, 5760, 122753, 2, {"qkv": 4, "o": 8, "up": 8, "down": 4})


def test_peaks_of_v5e():
    p = work.load_peaks("TPU v5 lite")
    assert (p["int8_ops_per_s"], p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        393e12, 197e12, 819e9, 16e9)


def test_unknown_device_is_an_error():
    with pytest.raises(work.UnknownDevice):
        work.load_peaks("TPU v99")


def test_qmatmul_counts_true_shape_at_weight_bits():
    # Ouro qkv, one decode row: 2·2048·6144 ops; x 2048 + w4 2048·6144/2 + bias 4·6144 + out 6144
    assert work.qmatmul(1, 2048, 6144, 4) == (25_165_824.0, 2048 + 6_291_456 + 24_576 + 6144)
    assert work.qmatmul(3, 2304, 2304, 8) == (2 * 3 * 2304 * 2304, 3 * 2304 + 2304 * 2304 + 4 * 2304 + 3 * 2304)


def test_attention_counts_causal_pairs_at_true_head_dim():
    # 192 prompt tokens: 192·193/2 = 18528 causal pairs, 4·128 ops each
    assert work.attention_prefill(192, 128) == (9_486_336.0, 4 * 192 * 128)
    # two slots with contexts 10 and 20 at head dim 64 (MiniCPM), no padding to 128
    assert work.attention_decode([10, 20], 64) == (4 * 64 * 30, 2 * 64 * 30 + 2 * 64 * 2)


def test_ouro_prefill_needed_ops():
    w = work.TokenPathWork(OURO, work.load_peaks("TPU v5 lite"))
    w.prefill(192)
    per_layer = 2 * 192 * (2048 * 6144 + 2048 * 2048 + 2 * 2048 * 5632)
    attention = 2 * 16 * 9_486_336
    lm_head = 2 * 2048 * 49152  # the last prompt position only
    assert w.needed_ops == 2 * per_layer + attention + lm_head == 31_106_531_328
    assert w.kernels["qmatmul"].calls == 8 and w.kernels["qattention"].calls == 32


def test_minicpm_decode_needed_ops_and_roofline():
    peaks = work.load_peaks("TPU v5 lite")
    w = work.TokenPathWork(MINICPM, peaks)
    w.decode([99, 199])  # two live slots, contexts 100 and 200
    matmuls = 2 * 2 * 2 * (2304 * 6912 + 2304 * 2304 + 2 * 2304 * 5760)
    attention = 2 * 36 * 4 * 64 * 300
    lm_head = 2 * 2 * 2304 * 122753
    assert w.needed_ops == matmuls + attention + lm_head == 1_519_027_200
    # two rows: every projection is bound by its weight bytes
    per_layer_bytes = (
        (2 * 2304 + 2304 * 6912 / 2 + 4 * 6912 + 2 * 6912)
        + (2 * 2304 + 2304 * 2304 + 4 * 2304 + 2 * 2304)
        + (2 * 2304 + 2304 * 5760 + 4 * 5760 + 2 * 5760)
        + (2 * 5760 + 5760 * 2304 / 2 + 4 * 2304 + 2 * 2304)
    )
    assert w.kernels["qmatmul"].roofline_s == pytest.approx(2 * per_layer_bytes / 819e9, rel=1e-12)


def test_no_live_slot_is_no_work():
    w = work.TokenPathWork(OURO, work.load_peaks("TPU v5 lite"))
    w.decode([])
    assert w.needed_ops == 0 and w.kernels["qmatmul"].calls == 0
