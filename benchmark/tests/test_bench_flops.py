"""FLOPs and bytes from shapes, against numbers worked by hand."""

import pytest

from benchmark.flops import flash, model
from benchmark.harness import manifest as mf
from benchmark.harness.peaks import PEAKS, peak


def sizes(name):
    return mf.load_config(mf.load_manifest(), name)["sizes"]


def test_gpt2_medium_flops_per_token():
    s = sizes("gpt2-medium")
    # by hand: a layer holds 4*(1024^2+1024) + 2*1024*4096 + 4096 + 1024
    # + 4*1024 = 12,596,224 parameters; 24 of them, the final norm (2048)
    # and the 1024 x 50257 output matrix: 353,774,592
    assert model.non_embedding_params(s) == 353_774_592
    # 6 N + 6 L dim seq = 2,122,647,552 + 150,994,944
    assert model.train_flops_per_token(s, 1024) == 2_273_642_496
    assert round(model.train_flops_per_token(s, 1024) / 1e9, 3) == 2.274


def test_gpt2_xl_flops_per_token():
    s = sizes("gpt2-xl")
    assert model.non_embedding_params(s) == 1_555_972_800
    assert round(model.train_flops_per_token(s, 1024) / 1e9, 3) == 9.808
    # the attention term grows with the sequence, the rest does not
    assert (model.train_flops_per_token(s, 2048)
            - model.train_flops_per_token(s, 1024)) == 6 * 48 * 1600 * 1024


def test_flash_call_costs():
    # gpt2-medium, 2 rows: 2 rows x 16 heads x 1024^2 x 64 x 2 FLOPs a
    # product = 4.295e9; two products, half under the causal mask
    f, b = flash.fwd(2, 1024, 16, 64)
    assert f == 2 * 0.5 * 2 * 2 * 16 * 1024 * 1024 * 64
    tensor = 2 * 1024 * 16 * 64 * 2
    assert b == 4 * tensor + 2 * 16 * 1024 * 4
    fb, bb = flash.bwd(2, 1024, 16, 64)
    assert fb == 2.5 * f and bb == 8 * tensor + 2 * 16 * 1024 * 4
    assert flash.fwd(2, 1024, 16, 64, causal=False)[0] == 2 * f


def test_flash_roofline_names_its_bound():
    chip = peak("TPU v5 lite")
    t, bound = flash.least_seconds(*flash.fwd(2, 1024, 16, 64), chip)
    assert bound == "flops" and t == pytest.approx(4.295e9 / 197e12, rel=1e-3)
    t, bound = flash.least_seconds(1e6, 819e9, chip)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_peaks_are_the_published_v5e_and_nothing_is_guessed():
    v5e = peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12       # not int8's 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["source"] == "Google Cloud documentation, TPU v5e"
    assert all("source" in p for p in PEAKS.values())
    for kind in ("cpu", "TPU v4", "TPU v5", ""):
        with pytest.raises(KeyError, match="no peaks on record"):
            peak(kind)
