"""Work counts from shapes."""
import pytest

from chipbench.harness import work
from chipbench.harness.peaks import PEAKS, peaks

GPT2_LARGE = {"n_layer": 36, "n_embd": 1280, "n_head": 20, "n_inner": None,
              "vocab_size": 50257}


def test_gpt2_large_position_flops():
    # 36 layers x 2 x 12 d^2 (q, k, v, o and a 4d MLP): 2 x 708 M params
    body = 36 * 2 * 12 * 1280 ** 2
    assert work.gpt2_position_flops(GPT2_LARGE, 0) == body
    assert work.gpt2_position_flops(GPT2_LARGE, 100) == \
        body + 36 * 4 * 100 * 1280
    assert work.gpt2_head_flops(GPT2_LARGE) == 2 * 1280 * 50257


def test_token_flops_count_the_prompt_once():
    first = work.gpt2_token_flops(GPT2_LARGE, 48, 0)
    later = work.gpt2_token_flops(GPT2_LARGE, 48, 5)
    head = work.gpt2_head_flops(GPT2_LARGE)
    assert first == pytest.approx(
        sum(work.gpt2_position_flops(GPT2_LARGE, c) for c in range(1, 49))
        + head)
    assert later == work.gpt2_position_flops(GPT2_LARGE, 53) + head
    # about 1.6 GFLOP per decoded token at this length
    assert 1.5e9 < later < 1.7e9


def test_kbest_work_and_roofline():
    ops, nbytes = work.kbest_dp_work(R=1, P=36, L=36, K=4)
    assert ops == 2 * 1 * 36 * 4
    assert nbytes == 4 * (2 * 36 + 36 + 3 * 37 * 4)
    peak = peaks("TPU v5 lite")
    t = work.roofline_seconds(ops, nbytes, peak)
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_has_no_peaks():
    assert PEAKS["TPU v5 lite"]["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
