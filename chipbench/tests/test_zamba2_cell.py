"""The Zamba2 cell at a test size on the CPU: the plain reference against
the program, ``correct`` on a sound run and on the float8 control, the
work counts by hand, and the two Zamba2 trace readers on a hand-made
run. The tiny configuration keeps two B/C groups, two shared blocks and
two hybrid layers."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control
from chipbench import run as bench
from chipbench.harness import cell as cells
from chipbench.harness import work
from chipbench.harness import work_zamba2 as wz
from chipbench.harness.cell import BENCH, load_json
from chipbench.harness.peaks import peaks
from chipbench.tests.test_correct import _cell
from chipbench.tests.test_metrics import _ctx, _read
from chipbench.tests.test_reference import SEED, _program

TINY_ZAMBA2 = load_json(BENCH / "tests" / "data" / "tiny.zamba2-3x2.json")
#: float32 program against the float32 reference: the two differ only in
#: the order of operations (chunked scan against the recurrence, fused
#: projections), a few float32 ulps of logits of size ~0.5
ZAMBA2_ATOL = 2e-5
ZAMBA2 = {"n_layer": 12, "hidden_size": 3584, "mamba_expand": 2,
          "mamba_headdim": 64, "mamba_d_state": 64, "mamba_ngroups": 2,
          "mamba_d_conv": 4, "num_attention_heads": 32,
          "num_key_value_heads": 32, "attention_head_dim": 224,
          "intermediate_size": 14336, "vocab_size": 32000, "adapter_rank": 128,
          "chunk_size": 256, "hybrid_layer_ids": [6, 11]}
SSD_OP = ("%ssd_chunk.3 = (f32[1,112,512,64]{3,2,1,0}, f32[1,112,64,64]) "
          'custom-call(...), custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------


def test_zamba2_reference_makes_the_programs_weights():
    ref = cells.reference(TINY_ZAMBA2)
    _, model = _program(TINY_ZAMBA2)
    want = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    got = ref.init_params(SEED, TINY_ZAMBA2["model"])
    mixer, shared = want["mamba"]["mixer"], want["shared"]
    pairs = [(got["tok"], want["embed"]["tok"])]
    pairs += [(got["mixers"][k], mixer[w]) for k, w in (
        ("in_proj", "in_proj"), ("out_proj", "out_proj"), ("conv", "conv_w"),
        ("A_log", "A_log"), ("dt_bias", "dt_bias"))]
    pairs += [(got["shared"][k], shared["attn"][k])
              for k in ("wq", "wk", "wv", "wo")]
    pairs += [(got["shared"][k], shared["mlp"][w]) for k, w in (
        ("gate", "wi"), ("up", "wg"), ("down", "wo"))]
    pairs += [(got["own"][k], want["hybrid"][k])
              for k in ("linear", "lora_a", "lora_b")]
    for g, w in pairs:
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert np.all(np.asarray(mixer["D"]) == 1.0)
    assert np.all(np.asarray(mixer["conv_b"]) == 0.0)
    assert np.all(np.asarray(shared["norm"]["weight"]) == 1.0)


def test_zamba2_reference_logits_match_the_program_in_float32():
    """The reference's logits at every position against the program's
    prefill (last position) and full forward (every position)."""
    from repro.models import zamba2

    ref = cells.reference(TINY_ZAMBA2)
    mc, model = _program(TINY_ZAMBA2)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(0).integers(0, mc.vocab_size, (2, 40))
    got = np.asarray(ref.logits(ref.init_params(SEED, TINY_ZAMBA2["model"]),
                                tokens, TINY_ZAMBA2["model"]))
    with jax.default_matmul_precision("highest"):
        x, _, _ = zamba2.forward_hidden(mc, params, jnp.asarray(tokens))
        full = zamba2.logits_head(mc, params["embed"], x)
        np.testing.assert_allclose(got, np.asarray(full), atol=ZAMBA2_ATOL)
        for t in (1, 7, 16, 17, 40):     # inside, at and past a chunk
            want, _ = model.prefill(params, tokens=jnp.asarray(tokens[:, :t]))
            np.testing.assert_allclose(got[:, t - 1], np.asarray(want[:, -1]),
                                       atol=ZAMBA2_ATOL)


def test_zamba2_prefill_then_decode_matches_the_reference():
    """Prefill of a prompt, then decode through the cache (conv and SSM
    states, the hybrid layers' KV), against the reference's full forward
    at each decoded position."""
    ref = cells.reference(TINY_ZAMBA2)
    mc, model = _program(TINY_ZAMBA2)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(2).integers(0, mc.vocab_size, (2, 30))
    got = np.asarray(ref.logits(ref.init_params(SEED, TINY_ZAMBA2["model"]),
                                tokens, TINY_ZAMBA2["model"]))
    with jax.default_matmul_precision("highest"):
        lg, cache = model.prefill(params, tokens=jnp.asarray(tokens[:, :21]),
                                  capacity=32)
        np.testing.assert_allclose(got[:, 20], np.asarray(lg[:, 0]),
                                   atol=ZAMBA2_ATOL)
        for t in range(21, 30):
            lg, cache = model.decode_step(params, jnp.asarray(tokens[:, t:t + 1]),
                                          cache)
            np.testing.assert_allclose(got[:, t], np.asarray(lg[:, 0]),
                                       atol=ZAMBA2_ATOL)


def test_zamba2_control_rounding_changes_the_logits():
    ref = cells.reference(TINY_ZAMBA2)
    params = ref.init_params(SEED, TINY_ZAMBA2["model"])
    tokens = np.random.default_rng(1).integers(0, 512, (2, 16))
    a = np.asarray(ref.logits(params, tokens, TINY_ZAMBA2["model"]))
    b = np.asarray(ref.logits(params, tokens, TINY_ZAMBA2["model"],
                              "float8_e4m3fn"))
    assert np.max(np.abs(a - b)) > 1e-2


def test_zamba2_run_queue_serves_the_references_greedy_tokens():
    """A tiny Zamba2 ``GTRACPipelineServer`` (three stages, golden peers),
    float32 activations: the tokens ``run_queue`` emits are the
    reference's greedy continuation."""
    from repro.configs.base import GTRACConfig
    from repro.serving.api import SubmitSpec
    from repro.serving.gtrac_serve import GTRACPipelineServer

    ref = cells.reference(TINY_ZAMBA2)
    mc, model = _program(TINY_ZAMBA2)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    srv = GTRACPipelineServer(mc, params, 2, replicas={"golden": 2},
                              gcfg=GTRACConfig(**TINY_ZAMBA2["gtrac"]))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, mc.vocab_size, n).astype(np.int32)
               for n in (6, 7)]
    with jax.default_matmul_precision("highest"):
        reqs = [srv.submit(SubmitSpec(prompt=p, max_new_tokens=4))
                for p in prompts]
        srv.run_queue()
    rp = ref.init_params(SEED, TINY_ZAMBA2["model"])
    for p, req in zip(prompts, reqs):
        seq = np.zeros((1, 12), np.int32)        # causal: zeros after a
        seq[0, :len(p)] = p                      # prefix change nothing
        for t in range(len(p), len(p) + 4):
            lg = ref.logits(rp, seq, TINY_ZAMBA2["model"])
            seq[0, t] = int(np.argmax(np.asarray(lg)[0, t - 1]))
        assert list(req.output) == list(seq[0, len(p):len(p) + 4])


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------


def test_zamba2_sound_run_is_correct_and_its_control_fails():
    """The tiny Zamba2 deployment (3 stages of 2 layers, two shared
    blocks) served as on the chip is correct; the float8 control in its
    place is not."""
    cell = _cell("tiny.zamba2-3x2")
    seed = 2 ** 31 + 11
    out = bench.serve(cell, seed, 0, False, require_tpu=False,
                      t_start=time.perf_counter(), log=lambda m: None)
    checks, ok = bench.check(cell, out, seed, log=lambda m: None)
    assert ok, checks
    c_checks, c_ok = bench.check(cell, control.control_outcome(cell, out,
                                                               seed),
                                 seed, log=lambda m: None)
    assert not c_ok
    assert c_checks["logit_gap"]["value"] > c_checks["logit_gap"]["limit"]


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------


def test_zamba2_position_flops_by_hand():
    d, di, ch = 3584, 7168, 7168 + 2 * 2 * 64
    mixer = 2 * d * (di + ch + 112) + 2 * 4 * ch + 5 * 112 * 64 * 64 \
        + 2 * di * d
    assert wz.mixer_position_flops(ZAMBA2) == mixer
    # q, k, v from 7168 to 7168, o_proj, gate_up, down, LoRA, linear
    block = (2 * 7168 * 3 * 7168 + 2 * 7168 * d + 2 * d * 2 * 14336
             + 2 * 14336 * d + 2 * 128 * (d + 2 * 14336) + 2 * d * d)
    assert wz.shared_position_flops(ZAMBA2, 0) == block
    assert wz.shared_position_flops(ZAMBA2, 100) == block + 4 * 100 * 7168
    assert wz.position_flops(ZAMBA2, 0) == 12 * mixer + 2 * block
    # the matmuls of a position: ~3.29 GFLOP, 41% of them in the blocks
    assert 3.2e9 < wz.position_flops(ZAMBA2, 0) < 3.4e9
    assert wz.head_flops(ZAMBA2) == 2 * d * 32000
    first = wz.token_flops(ZAMBA2, 48, 0)
    assert first == pytest.approx(sum(wz.position_flops(ZAMBA2, c)
                                      for c in range(1, 49))
                                  + wz.head_flops(ZAMBA2))
    assert wz.token_flops(ZAMBA2, 48, 5) == \
        wz.position_flops(ZAMBA2, 53) + wz.head_flops(ZAMBA2)


def test_zamba2_ssd_work_by_hand():
    H, P, N, G = 112, 64, 64, 2

    def chunk(c):
        return 2 * c * c * N * G + H * (2 * c * c * P + 4 * c * N * P)

    ops, nbytes = wz.ssd_chunked_work(ZAMBA2, 300)   # chunks of 256 and 44
    assert ops == chunk(256) + chunk(44)
    assert nbytes == 4 * (2 * 300 * H * P + 300 * H + 2 * 300 * G * N
                          + H * N * P)
    ops, nbytes = wz.ssd_step_work(ZAMBA2)
    assert ops == 5 * H * N * P
    assert nbytes == 4 * (2 * H * N * P + 2 * H * P + 2 * H + 2 * G * N)
    # a step moves its state: memory-bound on the chip
    peak = peaks("TPU v5 lite")
    assert work.roofline_seconds(ops, nbytes, peak) == \
        pytest.approx(nbytes / 819e9)


# ---------------------------------------------------------------------------
# trace readers
# ---------------------------------------------------------------------------


def _zamba2_ctx(trace=False):
    ctx = _ctx(trace=trace)
    ctx.model = ZAMBA2
    if trace:
        ctx.trace.ops_s.update({SSD_OP: 0.02,
                                "%ssd_chunk_like.1 = f32[2] fusion()": 1.0})
    return ctx


def test_zamba2_trace_readers():
    """The Zamba2 mfu counts Zamba2's FLOPs; the scan's roofline share
    reads the ``%ssd_chunk`` custom calls only, against the chunked scan
    over each prompt and one step per later token, in every layer."""
    ctx = _zamba2_ctx(trace=True)
    flops = sum(wz.token_flops(ZAMBA2, p, i)
                for p, n in ((40, 3), (50, 2)) for i in range(n))
    assert _read("zamba2_mfu", ctx) == pytest.approx(100 * flops / 197e12)
    peak = peaks("TPU v5 lite")
    step = work.roofline_seconds(*wz.ssd_step_work(ZAMBA2), peak)
    need = 12 * sum(work.roofline_seconds(*wz.ssd_chunked_work(ZAMBA2, p),
                                          peak) + step * (n - 1)
                    for p, n in ((40, 3), (50, 2)))
    assert _read("ssd_chunk_roofline", ctx) == pytest.approx(
        100 * need / 0.02)


@pytest.mark.parametrize("name", ["zamba2_mfu", "ssd_chunk_roofline"])
def test_zamba2_trace_readers_read_nothing_without_a_trace(name):
    assert _read(name, _zamba2_ctx()) is None


def test_ssd_roofline_reads_nothing_without_the_kernel():
    """The jnp scan in the kernel's place: no custom call, no share."""
    ctx = _zamba2_ctx(trace=True)
    ctx.trace.ops_s = {"%fusion.7 = f32[1,512,112,64] fusion()": 0.5}
    assert _read("ssd_chunk_roofline", ctx) is None
