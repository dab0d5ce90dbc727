"""The plain references against the program at a test size, and the
K-shortest-path reference against brute force."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import cell as cells
from chipbench.harness.cell import BENCH, load_json

TINY = load_json(BENCH / "tests" / "data" / "tiny.golden-18x2.json")
SEED = 2 ** 31 + 7


def _program(cfg, activation="float32"):
    from repro.models.api import build_model

    mc = cells.adapter(cfg).model_config(cfg)
    mc = dataclasses.replace(mc, activation_dtype=activation)
    return mc, build_model(mc)


def test_reference_makes_the_programs_weights():
    ref = cells.reference(TINY)
    _, model = _program(TINY)
    want = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    got = ref.init_params(SEED, TINY["model"])
    layers = want["layers"]
    pairs = [(got["tok"], want["embed"]["tok"]),
             (got["pos"], want["embed"]["pos"])]
    for a, b in (("wq", "wq"), ("wk", "wk"), ("wv", "wv"), ("wo", "wo")):
        pairs.append((got["blocks"][a], layers["attn"][b]))
    pairs += [(got["blocks"]["w_in"], layers["ffn"]["wi"]),
              (got["blocks"]["w_out"], layers["ffn"]["wo"])]
    for g, w in pairs:
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert np.all(np.asarray(layers["norm1"]["weight"]) == 1.0)
    assert np.all(np.asarray(layers["norm1"]["bias"]) == 0.0)


def test_reference_logits_match_the_program_in_float32():
    ref = cells.reference(TINY)
    mc, model = _program(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(0).integers(0, mc.vocab_size, (2, 12))
    got = ref.logits(ref.init_params(SEED, TINY["model"]), tokens,
                     TINY["model"])
    for t in (1, 5, 12):
        with jax.default_matmul_precision("highest"):
            want, _ = model.prefill(params, tokens=jnp.asarray(tokens[:, :t]))
        np.testing.assert_allclose(np.asarray(got[:, t - 1]),
                                   np.asarray(want[:, -1]), atol=2e-5,
                                   rtol=1e-4)


def test_control_rounding_changes_the_logits():
    ref = cells.reference(TINY)
    params = ref.init_params(SEED, TINY["model"])
    tokens = np.random.default_rng(1).integers(0, 512, (2, 16))
    a = np.asarray(ref.logits(params, tokens, TINY["model"]))
    b = np.asarray(ref.logits(params, tokens, TINY["model"],
                              "float8_e4m3fn"))
    assert np.max(np.abs(a - b)) > 1e-3


def _all_paths(starts, ends, ok, L):
    paths = []

    def walk(b, acc):
        if b == L:
            paths.append(acc)
            return
        for p in np.nonzero(ok & (starts == b))[0]:
            walk(int(ends[p]), acc + [int(p)])

    walk(0, [])
    return paths


@pytest.mark.parametrize("seed", range(6))
def test_kbest_reference_matches_brute_force(seed):
    kb = cells.load_module(BENCH / "references" / "kbest.py", "ref_kbest")
    rng = np.random.default_rng(seed)
    L = 6
    segs = [(s, e) for s, e in itertools.combinations(range(L + 1), 2)
            if e - s <= 3]
    starts = np.array([s for s, _ in segs for _ in range(2)])
    ends = np.array([e for _, e in segs for _ in range(2)])
    P = len(starts)
    lat = rng.integers(20, 400, P).astype(float)
    trust = np.round(rng.uniform(0.75, 1.0, P) * 8) / 8
    alive = rng.random(P) > 0.1
    ok = kb.usable(alive, trust, 0.9, starts, ends, L)
    c = kb.edge_costs(lat, trust, 25_000.0)
    want = sorted(sum(c[p] for p in path)
                  for path in _all_paths(starts, ends, ok, L))[:4]
    assert kb.kbest(starts, ends, c, ok, L, 4) == pytest.approx(want)
