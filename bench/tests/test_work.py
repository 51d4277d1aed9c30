"""Work counts against hand counts: qwen3-0.6b at the cells' cut (20
layers, K=4 agents, 8192 tokens per step of 512) and at the 16-layer,
2048-token shape of the first chip runs."""

import pytest

from bench import work
from bench.tests.conftest import BENCH, load


@pytest.fixture
def cfg():
    return load(BENCH / "configs" / "qwen3-0.6b-dp.json")


def test_model_flops_per_step(cfg):
    # 6 x (16 x 15.73 M + 155.58 M head) x 2048 = 5.005e12, plus
    # 12 x 16 x 2048 x 256 x 16 x 128 / 2 = 0.103e12 for attention
    cut16 = dict(cfg, num_hidden_layers=16)
    assert work.train_flops(cut16, 2048, 256) == pytest.approx(5.108e12,
                                                               rel=1e-3)


def test_model_flops_per_step_of_the_cells(cfg):
    # 6 x (20 x 15.73 M + 155.58 M head + final norm) x 8192 = 23.112e12,
    # plus 12 x 20 x 8192 x 512 x 16 x 128 / 2 = 1.031e12 for attention
    mix = load(BENCH / "traffic" / "rsmm.json")
    tokens = 4 * mix["seqs_per_agent"] * mix["seq_len"]
    assert cfg["num_hidden_layers"] == 20 and tokens == 8192
    assert work.train_flops(cfg, tokens, mix["seq_len"]) == pytest.approx(
        23.112e12 + 1.031e12, rel=1e-4)


def test_coordinates_and_bytes_per_rs_mm_step(cfg):
    m = work.aggregated_coords(cfg)
    # embedding + head at 152064 x 1024, 20 layers of 15.73 M, final norm
    assert m == 2 * 152064 * 1024 + 20 * 15730944 + 1024
    assert m / 1e6 == pytest.approx(626.0, abs=0.05)
    assert work.mm_bytes(4, m) / 1e9 == pytest.approx(12.52, abs=0.005)
    cut16 = work.aggregated_coords(dict(cfg, num_hidden_layers=16))
    assert cut16 / 1e6 == pytest.approx(563.1, abs=0.05)
    assert work.mm_bytes(4, cut16) / 1e9 == pytest.approx(11.26, abs=0.005)


def test_unknown_device_has_no_peaks():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
