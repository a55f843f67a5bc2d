"""Needed work against hand-worked shapes."""
import pytest

from chipbench import peaks, work

ARCH = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
        "d_head": 2, "d_ff": 16, "vocab": 100}
FMT = {"": {"k": 12, "emax": 15, "emin": -24},
       "layer*/attn": {"k": 11, "emax": 15, "emin": -24},
       "layer1": {"k": 13, "emax": 15, "emin": -24}}


@pytest.mark.parametrize("emax,emin,e", [(15, -14, 5), (15, -24, 6),
                                         (127, -126, 8), (7, -6, 4)])
def test_exponent_bits(emax, emin, e):
    assert work.exponent_bits(emax, emin) == e


def test_scope_formats_resolve_like_the_map():
    assert work.bits(FMT, ["layer0", "mlp"]) == 18        # default k 12
    assert work.bits(FMT, ["layer0", "attn"]) == 17       # layer*/attn
    assert work.bits(FMT, ["layer1", "attn"]) == 17       # deeper key wins
    assert work.bits(FMT, ["layer1", "mlp"]) == 19        # layer1
    assert work.bits(None, ["layer1", "mlp"]) == 32


def test_decode_gemms_count_active_lanes_at_format_width():
    g = work.decode_gemms(ARCH, FMT, lanes=3)
    assert len(g) == 14
    # layer 0, q: [3, 8] @ [8, 8] at 17 bits
    assert g[0] == (2 * 3 * 8 * 8, (64 + 24 + 24) * 17 / 8)
    # layer 1, down: [3, 16] @ [16, 8] at 19 bits
    assert g[13] == (2 * 3 * 16 * 8, (128 + 48 + 24) * 19 / 8)


def test_decode_attention_counts_live_positions():
    # two lanes attending 5 and 9 live positions: kv = 14, not 2 * max_seq
    a = work.decode_attention(ARCH, FMT, lanes=2, kv=14)
    assert a[0] == (4 * 4 * 2 * 14, (2 * 14 * 2 * 2 + 2 * 2 * 4 * 2) * 17 / 8)
    assert len(a) == 2


def test_decode_flops_emit_one_logit_row_per_lane():
    per_layer = 2 * 3 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16)
    attn = 4 * 4 * 2 * 20
    head = 2 * 3 * 8 * 100
    assert work.decode_flops(ARCH, 3, 20) == 2 * (per_layer + attn) + head


def test_prefill_flops_are_causal_with_one_logit_row():
    P = 5
    per_layer = 2 * P * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16)
    attn = 4 * 4 * 2 * (P * (P + 1) / 2)
    assert work.prefill_flops(ARCH, P) == (2 * (per_layer + attn)
                                          + 2 * 8 * 100)


def test_roofline_takes_the_larger_bound_per_call():
    pk = peaks.peaks("TPU v5 lite")
    t = work.roofline_s([(197e12, 1.0), (1.0, 819e9)], pk)
    assert t == pytest.approx(2.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
