"""``flops.py`` against figures worked by hand for both configurations."""

import json
import os

import pytest

from chipbench import flops
from chipbench import run as bench_run


def _config(name):
    with open(os.path.join(bench_run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_parameters_by_hand():
    c = _config("cerebras-gpt-590m")
    # 18 x (4 x 1536^2 + 2 x 1536 x 6144) + 1536 x 50304
    assert flops.matmul_params(c) == 18 * 28_311_552 + 77_266_944
    assert flops.matmul_params(c) == 586_874_880
    c = _config("cerebras-gpt-1.3b")
    # 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 2048 x 50304
    assert flops.matmul_params(c) == 24 * 50_331_648 + 103_022_592
    assert flops.matmul_params(c) == 1_310_982_144


def test_train_flops_per_token_by_hand():
    c = _config("cerebras-gpt-590m")
    # 6 x 586,874,880 + 6 x 18 x 1536 x 2048
    assert flops.train_flops_per_token(c, 2048) == (
        3_521_249_280 + 339_738_624)
    c = _config("cerebras-gpt-1.3b")
    assert flops.train_flops_per_token(c, 2048) == (
        6 * 1_310_982_144 + 6 * 24 * 2048 * 2048)


def test_flash_counts_by_hand():
    # batch 4, 12 heads of 128, t = 2048: 2048 x 2049 / 2 pairs a head
    pairs = 4 * 12 * 2_098_176
    ops, nbytes = flops.flash_fwd(4, 12, 128, 2048)
    assert ops == 4 * pairs * 128 and nbytes == 4 * 4 * 12 * 2048 * 128 * 2
    ops_b, bytes_b = flops.flash_bwd(4, 12, 128, 2048)
    assert ops_b * 2 == ops * 5 and bytes_b == 2 * nbytes
    peak = flops.peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(ops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(ops / 197e12)


def test_paged_attention_counts_by_hand():
    c = _config("cerebras-gpt-1.3b")
    assert flops.kv_bytes_per_token(c) == 2 * 24 * 2048 * 2 == 196_608
    ops, nbytes = flops.paged_attention_live(c, [100, 600])
    assert nbytes == 700 * 196_608 and ops == 4 * 24 * 2048 * 700
    _, bound = flops.roofline_seconds(ops, nbytes, flops.peaks("TPU v5 lite"))
    assert bound == "memory"


@pytest.mark.parametrize("name, rows, matmul, train_2k, kv, live", [
    ("cerebras-gpt-590m", 50304, 586_874_880, 3_860_987_904, 110_592,
     (77_414_400, 77_414_400)),
    ("cerebras-gpt-1.3b", 50304, 1_310_982_144, 8_469_872_640, 196_608,
     (137_625_600, 137_625_600)),
])
def test_counts_through_the_family_are_the_parents(name, rows, matmul,
                                                   train_2k, kv, live):
    """What ``flops.py`` returned at commit cdabbda, when it read
    ``n_layer`` / ``n_embd`` / ``n_inner`` itself (PR 27 moved the sizes
    behind ``chipbench/families/``)."""
    c = _config(name)
    assert "family" not in c  # absent means gpt2: the files did not change
    assert flops.vocab_rows(c) == rows
    assert flops.matmul_params(c) == matmul
    assert flops.train_flops_per_token(c, 2048) == train_2k
    assert flops.kv_bytes_per_token(c) == kv
    assert flops.paged_attention_live(c, [100, 600]) == live


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("_source")


def test_harrell_davis_quantile():
    import numpy as np

    from chipbench import stats

    # one value is every quantile; two values: the median is their mean
    assert stats.quantile_hd([3.0], 0.9) == 3.0
    assert stats.quantile_hd([1.0, 3.0], 0.5) == pytest.approx(2.0)
    # on a smooth sample it agrees with the interpolated percentile
    x = np.random.default_rng(0).lognormal(0.0, 0.6, 144)
    assert stats.quantile_hd(x, 0.9) == pytest.approx(
        np.percentile(x, 90), rel=0.05)
    # and it is a weighted mean of the sample: inside its range, and
    # moved by the slowest value
    assert x.min() < stats.quantile_hd(x, 0.9) < x.max()
    y = np.sort(x)
    y[-1] *= 10
    assert stats.quantile_hd(y, 0.9) > stats.quantile_hd(x, 0.9)
