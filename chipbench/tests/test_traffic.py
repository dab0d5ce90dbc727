"""The traffic generator: repeatable per seed, inside each mix's ranges,
and a warm-up walk that covers every length a mix can produce."""
import numpy as np
import pytest

from chipbench.harness import traffic
from chipbench.harness.cell import BENCH, load_json

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
VOCAB = 50257
SEED = 2 ** 31 + 12345


def _mix(name):
    return load_json(BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_wave_repeats_per_seed(name):
    mix = _mix(name)
    a = traffic.wave(mix, SEED, 3, VOCAB)
    b = traffic.wave(mix, SEED, 3, VOCAB)
    c = traffic.wave(mix, SEED + 1, 3, VOCAB)
    assert [(r.prompt.tolist(), r.max_new_tokens) for r in a] == \
        [(r.prompt.tolist(), r.max_new_tokens) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    assert len(a) == mix["wave_requests"]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_range(name):
    mix = _mix(name)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for seed in (0, 1, SEED):
        for i in range(20):
            for r in traffic.wave(mix, seed, i, VOCAB):
                assert p["min"] <= len(r.prompt) <= p["max"]
                assert o["min"] <= r.max_new_tokens <= o["max"]
                assert r.prompt.min() >= 0 and r.prompt.max() < VOCAB


@pytest.mark.parametrize("name", MIXES)
def test_waves_of_seeds_carry_the_same_work(name):
    """Stratified draws: a wave's total length moves little with the
    seed, so seeds change the tokens and not the amount of work."""
    mix = _mix(name)
    totals = [sum(len(r.prompt) + r.max_new_tokens
                  for r in traffic.wave(mix, s, 0, VOCAB))
              for s in range(50)]
    assert np.std(totals) / np.mean(totals) < 0.03


@pytest.mark.parametrize("name", MIXES)
def test_walk_covers_every_length_the_mix_produces(name):
    mix = _mix(name)
    produced = set()
    for seed in range(5):
        for i in range(50):
            for r in traffic.wave(mix, seed, i, VOCAB):
                n = len(r.prompt)
                produced |= set(range(n, n + r.max_new_tokens))
    lengths = set(traffic.prefix_lengths(mix))
    assert produced <= lengths
    walk = traffic.length_walk(mix, SEED, VOCAB)
    walked = set()
    for r in walk:
        n = len(r.prompt)
        walked |= set(range(n, n + r.max_new_tokens))
    assert walked == lengths
    p = mix["prompt_tokens"]
    assert {len(r.prompt) for r in walk} == set(range(p["min"], p["max"] + 1))


def test_walk_reaches_the_longest_sequence():
    """The walk's long stream ends at the longest prompt plus the longest
    output, so the last token append is warmed up too."""
    mix = _mix("chat")
    walk = traffic.length_walk(mix, 0, VOCAB)[0]
    assert len(walk.prompt) + walk.max_new_tokens == \
        mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
