import pytest

from harness import roofline
from harness.peaks import peaks

TOY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}
PEAK = {"hbm_bytes_per_s": 1000.0, "bf16_flops": 1e6}


def test_decode_step_bytes_by_hand():
    # a block: q and o 8*8 each, k and v 8*4 each = 192; ffn 3*8*16 = 384
    assert roofline.layer_weight_params(roofline.dims(TOY)) == 576
    # three blocks + the 8*100 head, one byte each
    assert roofline.decode_weight_bytes(TOY, 1.0) == 3 * 576 + 800
    # K and V of one position: 2 * 3 layers * 1 head * 4 * 2 bytes
    assert roofline.kv_bytes_per_token(TOY, 2.0) == 48
    least = roofline.decode_step_least_s(TOY, 10, PEAK)
    assert least == pytest.approx((2528 + 480) / 1000.0)
    # two stages x tp 2: a chip holds a quarter, the stages run in turn
    assert roofline.decode_step_least_s(TOY, 10, PEAK, stages=2, tp=2) \
        == pytest.approx(least / 2)


def test_attention_need_by_hand():
    # one decode row of context 10: K,V 2*10*1*4*2 = 160 bytes, q and
    # out 2*1*2*4*2 = 32; 10 pairs * 4 * 2 * 4 = 320 operations
    nbytes, ops = roofline.attention_need(TOY, [(1, 10)])
    assert (nbytes, ops) == (192, 320)
    # a 4-token window ending at context 6 sees 3+4+5+6 = 18 keys
    _b, ops = roofline.attention_need(TOY, [(4, 6)])
    assert ops == 4 * 2 * 4 * 18
    assert roofline.least_s(192, 320, PEAK) == (0.192, "bandwidth")
    assert roofline.least_s(1, 5e6, PEAK) == (5.0, "compute")


def test_an_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9")
