"""Operation and byte counts at both configurations' published widths,
against numbers worked out by hand."""
import json
import pathlib
import types

import pytest

from bench import roofline as peaks
from bench.families import arch_of, dense

# the counts moved into the dense family and the peaks stayed; the
# assertions read both under the name they had before the move
roofline = types.SimpleNamespace(**{**vars(peaks), **vars(dense)})

ROOT = pathlib.Path(__file__).resolve().parents[2]


def arch(name):
    return arch_of(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_qwen3_32b_decode_tick_over_8_layers():
    a = arch("qwen3-32b")
    # one layer: q 5120*8192 + k,v 2*5120*1024 + o 8192*5120
    # + MLP 3*5120*25600 + two norms 2*5120 + qk-norm 2*128
    assert roofline.layer_params(a) == 487_598_336
    # 8 layers, final norm 5120, untied head 5120*151936
    assert roofline.weight_bytes(a) == 2 * 4_678_704_128
    assert roofline.kv_bytes_per_token(a) == 32_768     # 8 layers * 4 KiB
    # one tick, two live sequences of 100 and 300 tokens
    flops, byt = roofline.decode_work(a, k=1, rows=2, ctx=400)
    lin = 2 * 8 * 487_587_840            # per token, norms excluded
    head = 2 * 5120 * 151_936
    attn = 4 * 8 * 64 * 128 * (101 + 301)
    assert flops == 2 * (lin + head) + attn == 18_819_842_048
    # weights once, two embedding rows, the live K/V read, two tokens' K/V
    assert byt == 9_357_408_256 + 2 * 2 * 5120 + 400 * 32_768 + 2 * 32_768
    # two fused ticks read the weights twice and one more key per row
    f2, b2 = roofline.decode_work(a, k=2, rows=2, ctx=400)
    assert b2 == 2 * 9_357_408_256 + 4 * 2 * 5120 + (400 + 402) * 32_768 \
        + 4 * 32_768
    assert f2 == 4 * (lin + head) + 4 * 8 * 64 * 128 * (402 + 404)


def test_chatglm3_6b_prefill_chunk():
    a = arch("chatglm3-6b")
    # q 4096*4096 + k,v 2*4096*256 + o 4096*4096 + MLP 3*4096*13696
    # + two norms 2*4096 + QKV bias 4096 + 2*256
    assert roofline.layer_params(a) == 203_960_832
    assert roofline.kv_bytes_per_token(a) == 2 * 2 * 28 * 2 * 128
    # 512 prompt tokens after 1024 cached ones, logits of the last row
    flops, byt = roofline.prefill_work(a, s=512, start=1024)
    pairs = 512 * 1024 + 512 * 513 // 2
    assert pairs == 655_616
    assert flops == 512 * 2 * 28 * 203_948_032 \
        + 4 * 28 * 32 * 128 * 655_616 + 2 * 4096 * 65_024
    assert byt == 2 * (28 * 203_960_832 + 4096 + 4096 * 65_024) \
        + 512 * 2 * 4096 + (1024 + 512) * 28_672


def test_least_time_takes_the_binding_bound():
    p = roofline.peaks("TPU v5 lite")
    assert roofline.least_time(197e12, 1, p) == pytest.approx(1.0)
    assert roofline.least_time(1, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
