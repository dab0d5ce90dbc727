"""Zamba2's hybrid block through the routed pipeline, at a tiny size that
keeps two B/C groups, two shared blocks and two hybrid layers: the stage
chain against the unsplit forward, what each stage holds, and the dtypes
of the served copies."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.pipeline import StagePartition
from repro.models import zamba2
from repro.models.api import build_model
from repro.obs.metrics import MetricsRegistry
from repro.serving.gtrac_serve import (
    make_stage_fns,
    served_stage_params,
    shared_block_bytes,
    stage_step,
)

KEY = jax.random.PRNGKey(3)


def _tiny(**kw):
    """Six layers, hybrid at 2 and 5 (blocks 0 and 1), two groups."""
    cfg = get_config("zamba2-7b").reduced(
        **{"num_layers": 6, "hybrid_layer_ids": (2, 5), **kw})
    assert cfg.ssm_n_groups == 2 and cfg.num_mem_blocks == 2
    return cfg


def _forward_logits(cfg, params, tokens):
    x, _, _ = zamba2.forward_hidden(cfg, params, tokens)
    return zamba2.logits_head(cfg, params["embed"], x)


def test_stage_chain_matches_the_unsplit_forward():
    """Three stages of two layers, chained as the executor chains hops,
    give the unsplit forward's last logits; in float32 only the order of
    operations differs (tolerance: a few float32 ulps of logits ~1)."""
    cfg = _tiny(activation_dtype="float32")
    params = build_model(cfg).init(KEY)
    tokens = jax.random.randint(KEY, (1, 19), 0, cfg.vocab_size)
    stages = served_stage_params(cfg, params, StagePartition.uniform(6, 2))
    x = None
    for i, sp in enumerate(stages):
        x = stage_step(cfg, sp, tokens, x, first=i == 0, last=i == 2)
    want = _forward_logits(cfg, params, tokens)[:, -1:]
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=1e-5)


def test_stage_fns_chain_the_hop_payload():
    cfg = _tiny()
    params = build_model(cfg).init(KEY)
    fns = make_stage_fns(cfg, params, StagePartition.uniform(6, 3))
    tokens = jax.random.randint(KEY, (1, 9), 0, cfg.vocab_size)
    payload = (tokens, None)
    for fn in fns:
        payload = fn(payload)
    assert payload[1].shape == (1, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(payload[1])))


def test_stage_fns_pad_each_hop_to_its_bucket():
    """Every length of one bucket runs the same three stage programs on
    right-padded positions, and the chain still gives the unsplit
    forward's last logits at the true length (float32)."""
    cfg = _tiny(activation_dtype="float32")
    params = build_model(cfg).init(KEY)
    fns = make_stage_fns(cfg, params, StagePartition.uniform(6, 3))
    tokens = jax.random.randint(KEY, (1, 2 * zamba2.STAGE_BUCKET), 0,
                                cfg.vocab_size)
    lengths = range(zamba2.STAGE_BUCKET + 1, zamba2.STAGE_BUCKET + 5)
    compiled = stage_step._cache_size()
    with jax.default_matmul_precision("highest"):
        want = _forward_logits(cfg, params, tokens)
        for S in lengths:
            payload = (tokens[:, :S], None)
            for fn in fns:
                payload = fn(payload)
                assert payload[0].shape == (1, S)      # tokens as they came
            np.testing.assert_allclose(np.asarray(payload[1][:, 0]),
                                       np.asarray(want[:, S - 1]), atol=1e-5)
    assert stage_step._cache_size() - compiled <= 3


def test_each_stage_holds_the_blocks_its_layers_use():
    """Stage [2, 4) holds hybrid layer 2 with block 0, stage [4, 6) layer
    5 with block 1, stage [0, 2) none; one cast copy of a block serves
    every stage that uses it, and the gauge counts it once per stage."""
    cfg = _tiny(num_layers=8, hybrid_layer_ids=(2, 5, 7))
    params = build_model(cfg).init(KEY)
    stages = served_stage_params(cfg, params, StagePartition.uniform(8, 2))
    assert [sorted(sp["hybrid"]) for sp in stages] == [[], [0], [1], [1]]
    block = lambda i, k: stages[i]["hybrid"][k]["block"]
    for i, k, b in ((1, 0, 0), (2, 1, 1), (3, 1, 0)):
        np.testing.assert_array_equal(
            np.asarray(block(i, k)["attn"]["wq"], np.float32),
            np.asarray(params["shared"]["attn"]["wq"][b].astype(
                jnp.bfloat16), np.float32))
    assert block(1, 0)["attn"]["wq"] is block(3, 1)["attn"]["wq"]
    one = sum(a.nbytes for a in jax.tree.leaves(block(1, 0)))
    assert shared_block_bytes(stages) == 3 * one
    obs = MetricsRegistry()
    make_stage_fns(cfg, params, StagePartition.uniform(8, 2), obs)
    assert obs.snapshot()["stage/shared_block_bytes"] == 3 * one


def test_served_dtypes():
    """Matmul weights and the embedding at bfloat16; A_log, D, dt_bias,
    the norms and the conv bias at float32."""
    cfg = _tiny()
    params = build_model(cfg).init(KEY)
    sp = served_stage_params(cfg, params, StagePartition.uniform(6, 3))[0]
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    mixer, hyb = sp["mamba"]["mixer"], sp["hybrid"][2]
    for a in (mixer["in_proj"], mixer["out_proj"], mixer["conv_w"],
              hyb["linear"], hyb["lora_a"], hyb["lora_b"],
              hyb["block"]["attn"]["wq"], hyb["block"]["attn"]["wo"],
              hyb["block"]["mlp"]["wi"], hyb["block"]["mlp"]["wg"],
              hyb["block"]["mlp"]["wo"], sp["embed"]["tok"]):
        assert a.dtype == bf16
    for a in (mixer["A_log"], mixer["D"], mixer["dt_bias"], mixer["conv_b"],
              mixer["gn_w"], sp["mamba"]["norm"]["weight"],
              hyb["block"]["norm"]["weight"],
              hyb["block"]["mlp_norm"]["weight"], sp["final_norm"]["weight"]):
        assert a.dtype == f32
    assert params["mamba"]["mixer"]["in_proj"].dtype == f32  # masters


@pytest.mark.parametrize("groups", [1, 2])
def test_gated_norm_groups(groups):
    """The gated RMSNorm normalises each group of d_inner / G channels."""
    from repro.models.mamba2 import _gated_rmsnorm
    y = jax.random.normal(KEY, (3, 16))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    out = _gated_rmsnorm(y, z, jnp.ones(16), groups)
    g = np.asarray(out).reshape(3, groups, -1)
    np.testing.assert_allclose(np.mean(g * g, -1), 1.0, atol=1e-3)


def test_gelu_gated_mlp():
    """act gelu_gated: down(gelu_exact(x @ wi + d0) * (x @ wg + d1))."""
    from repro.models.mlp import apply_mlp, init_mlp
    cfg = dataclasses.replace(_tiny(), activation_dtype="float32")
    p = init_mlp(KEY, cfg)
    x = jax.random.normal(KEY, (1, 4, cfg.d_model))
    d0 = jax.random.normal(jax.random.PRNGKey(5), (1, 4, cfg.d_ff))
    d1 = jax.random.normal(jax.random.PRNGKey(6), (1, 4, cfg.d_ff))
    with jax.default_matmul_precision("highest"):
        got = apply_mlp(cfg, p, x, delta=(d0, d1))
        h = jax.scipy.special.erf((x @ p["wi"] + d0) / np.sqrt(2.0))
        want = (0.5 * (x @ p["wi"] + d0) * (1 + h) * (x @ p["wg"] + d1)
                ) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_transformer_families_keep_their_stage_programs():
    """A GPT-2 stage lowers to the program that runs the layer scan over
    ``stage_params["layers"]``; the hybrid family's stage is its own."""
    cfg = get_config("gpt2-large").reduced(num_layers=4)
    params = build_model(cfg).init(KEY)
    stages = served_stage_params(cfg, params, StagePartition.uniform(4, 2))
    assert set(stages[0]) == {"embed", "final_norm", "layers"}
    tokens = jnp.zeros((1, 5), jnp.int32)
    text = stage_step.lower(cfg, stages[1], tokens,
                            jnp.zeros((1, 5, cfg.d_model), jnp.bfloat16),
                            first=False, last=False).as_text()
    assert "zamba2" not in text and "while" in text


def test_families_without_stages_refuse():
    cfg = get_config("rwkv6-1.6b").reduced()
    with pytest.raises(NotImplementedError):
        build_model(cfg).served_stages({}, [(0, 1)])
