"""The peaks table and the architectures' model-FLOP functions against
hand counts."""

import json

import pytest

import cell
import flops
import peaks


def _config(name):
    return json.load(open(cell.HERE / "configs" / f"{name}.json"))


def _arch(name):
    return cell.architecture(_config(name))


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


@pytest.mark.parametrize(
    "name, seq, per_layer, head, attn",
    [
        # h=2048, 16 q / 8 kv heads of 128, f=8192, V=92544
        ("internlm2-1.8b-2l", 4096,
         2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192, 2048 * 92544, 6 * 4096 * 16 * 128),
        ("internlm2-1.8b-8l", 1024,
         2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192, 2048 * 92544, 6 * 1024 * 16 * 128),
        # the one-chip configuration at the four-chip cell's sequence length
        ("internlm2-1.8b-2l", 1024,
         2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192, 2048 * 92544, 6 * 1024 * 16 * 128),
    ],
)
def test_train_flops_per_token_by_hand(name, seq, per_layer, head, attn):
    c, arch = _config(name), _arch(name)
    layers = c["num_hidden_layers"]
    assert arch.matmul_params(c) == layers * per_layer + head
    assert arch.train_flops_per_token(c, seq) == 6 * (layers * per_layer + head) + layers * attn
    assert flops.causal_attention(seq, 16, 128, 128) == attn


def test_internlm2_one_chip_flops_value():
    # 62.9M a layer, 189.5M in the head, attention at seq 4096: 1.99 GFLOP
    c = _config("internlm2-1.8b-2l")
    assert _arch("internlm2-1.8b-2l").train_flops_per_token(c, 4096) == 1992818688
