"""The benchmark's operation and byte counts against numbers worked by
hand for Mistral-7B's widths, and against the program's own today."""

import json
import os

import pytest

from conftest import ROOT
from perfbench import counts
from perfbench.drivers.train import program_config


def config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_train_flops_by_hand():
    cfg = config("mistral-7b-v0.1.train")
    d, dff, v, L = 4096, 14336, 32000, 4096
    qkv = 2 * d * (32 + 2 * 8) * 128          # 50.3 MFLOP
    out = 2 * d * d                           # 33.6
    attn = 4 * d * (L + 1) / 2                # causal mean of 2048.5 keys
    ffn = 6 * d * dff                         # 352.3
    forward = 2 * (qkv + out + attn + ffn) + 2 * d * v
    assert counts.train_flops_per_token(cfg, L) == pytest.approx(3 * forward)
    assert counts.train_flops_per_token(cfg, L) / 1e9 == pytest.approx(
        3.605, abs=0.001)
    four = dict(cfg, num_hidden_layers=4)     # ISSUE 24's own figure
    assert counts.train_flops_per_token(four, L) / 1e9 == pytest.approx(
        6.42, abs=0.005)
    assert counts.n_params(four) / 1e9 == pytest.approx(1.004, abs=0.001)
    assert counts.n_params(cfg) / 1e9 == pytest.approx(0.567, abs=0.001)
    assert counts.n_params(config("mistral-7b-v0.1.serve")) / 1e9 == \
        pytest.approx(2.748, abs=0.001)


@pytest.mark.parametrize("name,seq", [("mistral-7b-v0.1.train", 4096),
                                      ("mistral-7b-v0.1.train", 8192),
                                      ("mistral-7b-v0.1.serve", 512)])
def test_train_flops_equal_the_programs_count_today(name, seq):
    from lua_mapreduce_tpu.models.transformer import flops_per_token
    cfg = config(name)
    assert counts.train_flops_per_token(cfg, seq) == pytest.approx(
        flops_per_token(program_config(cfg), seq))


def test_window_bounds_the_visible_keys():
    cfg = dict(config("mistral-7b-v0.1.train"), sliding_window=4)
    assert [counts.visible_keys(cfg, p) for p in range(6)] == [1, 2, 3, 4, 4, 4]
    assert counts.mean_visible_keys(cfg, 6) == pytest.approx(18 / 6)


def test_decode_counts_by_hand():
    cfg = config("mistral-7b-v0.1.serve")
    # scanned positions 384..510 see 385..511 keys: 127 steps, 56,896 rows
    rows = sum(range(385, 512))
    assert rows == 56896
    nbytes = 2 * 2 * 32 * 8 * 128 * rows * 12
    assert counts.decode_kernel_bytes(cfg, 32, 384, 128) == nbytes
    assert counts.decode_kernel_flops(cfg, 32, 384, 128) == \
        4 * 32 * 32 * 128 * rows * 12
    # a request feeds 511 positions; without attention a position costs
    # 12 layers of projections and SwiGLU and one head
    no_attn = 12 * (2 * 4096 * 6144 + 2 * 4096 ** 2 + 6 * 4096 * 14336) \
        + 2 * 4096 * 32000
    attn = 12 * 4 * 4096 * sum(range(1, 512))
    assert counts.decode_request_flops(cfg, 32, 384, 128) == pytest.approx(
        32 * (511 * no_attn + attn))


def test_flash_counts_by_hand():
    cfg = config("mistral-7b-v0.1.train")
    pairs = 4096 * 4097 / 2
    assert counts.flash_train_flops(cfg, 3, 4096) == pytest.approx(
        14 * 3 * 32 * pairs * 128 * 2)
    q, kv = 3 * 4096 * 32 * 128, 3 * 4096 * 8 * 128
    assert counts.flash_train_bytes(cfg, 3, 4096) == 2 * (6 * q + 6 * kv) * 2
