"""Operations, bytes and peaks of the chip benchmark (``work.py``,
``peaks.json``, and the counts of ``archs/mlp.py``)."""
import json
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import work  # noqa: E402

MLP = harness.load_arch("mlp")
BINOMIAL = {"widths": [5, 512, 512, 1]}
MINIBUDE = {"widths": [6, 1024, 819, 655, 524, 419, 335, 1]}


@pytest.mark.parametrize("config,flops", [(BINOMIAL, 530_432),
                                          (MINIBUDE, 4_169_442)])
def test_flops_per_row(config, flops):
    assert MLP.flops_per_row(config) == flops


def test_flops_per_row_matches_the_configuration_files():
    for name, flops in (("binomial-mlp-5-512-512-1", 530_432),
                        ("minibude-mlp-6-1024-819-655-524-419-335-1",
                         4_169_442)):
        cfg = json.loads((CHIP / "configs" / f"{name}.json").read_text())
        assert harness.load_arch(cfg["arch"]).flops_per_row(cfg) == flops


def test_call_bytes():
    # weights + biases once, every input and output row once, f32
    params = 5 * 512 + 512 + 512 * 512 + 512 + 512 * 1 + 1
    assert MLP.call_bytes(BINOMIAL, 0) == 4 * params
    assert MLP.call_bytes(BINOMIAL, 32768) == 4 * (params + 32768 * 6)
    widths = MINIBUDE["widths"]
    params = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    assert MLP.call_bytes(MINIBUDE, 0) == 4 * params


def test_least_time_is_the_larger_bound():
    peak = work.peak_for("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    big = work.least_time_s(MLP.flops_per_row(BINOMIAL) * 32768,
                            MLP.call_bytes(BINOMIAL, 32768), peak)
    assert big == pytest.approx(530_432 * 32768 / 197e12)
    # one row: the weights' bytes bound it, not the FLOPs
    one = work.least_time_s(MLP.flops_per_row(BINOMIAL),
                            MLP.call_bytes(BINOMIAL, 1), peak)
    assert one == pytest.approx(MLP.call_bytes(BINOMIAL, 1) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak_for("cpu")
