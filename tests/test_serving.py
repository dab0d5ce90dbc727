"""Serving tests: engine correctness + G-TRAC routed pipeline produces the
same tokens as monolithic execution, and survives injected failures."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import GTRACConfig
from repro.distributed.pipeline import StagePartition
from repro.models.api import build_model
from repro.models.transformer import activation_dtype_params
from repro.serving.api import SubmitSpec
from repro.serving.engine import ServingEngine
from repro.serving.gtrac_serve import (
    GTRACPipelineServer,
    make_stage_fns,
    sample_token,
    served_stage_params,
    stage_step,
)

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("gpt2-large").reduced(num_layers=4, vocab_size=128,
                                           remat=False)
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params


def monolithic_greedy(cfg, model, params, prompt, n):
    """Reference: full-recompute greedy decode."""
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    out = []
    for _ in range(n):
        logits, _ = model.prefill(params, tokens=toks)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks = jnp.concatenate([toks, jnp.full((1, 1), nxt, jnp.int32)], 1)
    return out


class TestEngine:
    def test_engine_matches_monolithic(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(cfg, params)
        prompt = np.arange(1, 9)
        req = eng.submit(SubmitSpec(prompt=prompt, max_new_tokens=5))
        eng.run_batch([req])
        want = monolithic_greedy(cfg, model, params, prompt, 5)
        assert req.output == want

    def test_engine_batched_requests(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(cfg, params)
        reqs = [eng.submit(SubmitSpec(prompt=np.arange(1, 9) + i,
                              max_new_tokens=4))
                for i in range(3)]
        eng.run_batch(reqs)
        assert all(len(r.output) == 4 for r in reqs)


class TestGTRACServer:
    def test_routed_pipeline_matches_monolithic(self, tiny):
        """With only golden peers (no failures), the chain of real stage
        computations must reproduce monolithic greedy decoding exactly."""
        cfg, model, params = tiny
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, algorithm="gtrac",
                                  seed=0)
        prompt = np.arange(1, 9)
        out, met = srv.generate(prompt, max_new_tokens=5)
        want = monolithic_greedy(cfg, model, params, prompt, 5)
        assert list(out) == want
        assert met.failures == 0 and met.tokens == 5

    def test_middle_stages_share_one_executable(self):
        """Stage weights are arguments: five stages compile three programs
        (first, middle, last) per prefix length, and the chain's logits
        equal the monolithic model's."""
        cfg = get_config("gpt2-large").reduced(num_layers=5, vocab_size=96)
        model = build_model(cfg)
        params = model.init(KEY)
        fns = make_stage_fns(cfg, params, StagePartition.uniform(5, 1))
        compiles = []

        def count(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        toks = [jnp.arange(1, S + 1, dtype=jnp.int32)[None, :]
                for S in (6, 7)]
        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            for tokens in toks:
                payload = (tokens, None)
                for fn in fns:
                    payload = fn(payload)
                jax.block_until_ready(payload)
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        assert len(compiles) == 3 * len(toks)
        want, _ = model.prefill(params, tokens=toks[-1])
        np.testing.assert_allclose(np.asarray(payload[1]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("arch", ["gpt2-large", "phi3.5-moe-42b-a6.6b"],
                             ids=["gpt2-large", "moe"])
    def test_served_stage_weights_in_activation_dtype(self, arch):
        """The served stage copies hold the matmul weights and embedding
        tables at the activation dtype and every other leaf as stored;
        the chain's logits are bit-identical to those of the float32
        slices, and the server's gauges count the bytes per dtype."""
        cfg = get_config(arch).reduced(num_layers=4, vocab_size=128)
        params = build_model(cfg).init(KEY)
        part = StagePartition.uniform(cfg.num_layers, 2)
        served = served_stage_params(cfg, params, part)
        masters = [{**params, "layers": jax.tree.map(lambda a: a[s:e],
                                                     params["layers"])}
                   for s, e in map(part.segment, range(part.n_stages))]

        def chain(stages):
            tokens = jnp.arange(1, 10, dtype=jnp.int32)[None, :]
            x = None
            for i, sp in enumerate(stages):
                x = stage_step(cfg, sp, tokens, x, first=i == 0,
                               last=i == len(stages) - 1)
            return np.asarray(x)

        assert np.array_equal(chain(served), chain(masters))

        act, par = jnp.dtype(cfg.activation_dtype), jnp.dtype(cfg.param_dtype)
        assert act == jnp.bfloat16 and par == jnp.float32
        embed, layers = served[0]["embed"], served[0]["layers"]
        ffn = dict(layers["ffn"])
        router = ffn.pop("router", None)
        assert (router is None) == (cfg.family != "moe")
        cast = [*embed.values(), *layers["attn"].values(), *ffn.values()]
        kept = jax.tree.leaves([layers["norm1"], layers["norm2"],
                                served[0]["final_norm"], router])
        assert {a.dtype for a in cast} == {act}
        assert {a.dtype for a in kept} == {par}
        assert any(a.ndim == 1 for a in kept)        # biases or weights
        assert all(sp["embed"] is embed for sp in served)

        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 1}, seed=0)
        snap = srv.obs.snapshot()
        per_stage = [served[0]["embed"], served[0]["final_norm"],
                     *(sp["layers"] for sp in served)]
        for dt, name in ((act, "bf16"), (par, "f32")):
            want = sum(a.nbytes for a in jax.tree.leaves(per_stage)
                       if a.dtype == dt)
            assert snap[f"stage/weight_bytes_{name}"] == want > 0

        f32 = dataclasses.replace(cfg, activation_dtype="float32")
        same = activation_dtype_params(f32, params)
        assert all(a is b for a, b in zip(jax.tree.leaves(same),
                                          jax.tree.leaves(params)))

    def test_survives_injected_failures(self, tiny):
        """Honeypot-heavy peer pool: trust learning + repair keep serving."""
        cfg, model, params = tiny
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"honeypot": 2, "golden": 2},
                                  algorithm="gtrac", seed=1)
        done = 0
        for rid in range(6):
            out, met = srv.generate(np.arange(1, 9), max_new_tokens=4,
                                    request_id=rid)
            done += met.tokens == 4
        assert done >= 4  # converges to golden peers after early strikes

    def test_sp_baseline_worse_than_gtrac(self, tiny):
        cfg, model, params = tiny

        def run(algo, seed):
            srv = GTRACPipelineServer(
                cfg, params, layers_per_stage=2,
                replicas={"honeypot": 3, "golden": 1, "turtle": 1},
                algorithm=algo, seed=seed)
            ok = 0
            for rid in range(8):
                _, met = srv.generate(np.arange(1, 9), max_new_tokens=3,
                                      request_id=rid)
                ok += met.tokens == 3
            return ok / 8

        g = np.mean([run("gtrac", s) for s in range(2)])
        s = np.mean([run("sp", s) for s in range(2)])
        assert g >= s  # the honey-pot effect (paper §VI-A)

    def test_nongreedy_sampling_can_emit_non_argmax(self, tiny):
        """Regression: generate(greedy=False) was dead code — both
        branches of the conditional took argmax. Real temperature
        sampling off the testbed RNG must be able to leave the argmax
        chain (same params + prompt, so any divergence is sampling)."""
        cfg, model, params = tiny

        def build():
            return GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                       replicas={"golden": 2},
                                       algorithm="gtrac", seed=0)

        prompt = np.arange(1, 9)
        greedy_out, gm = build().generate(prompt, max_new_tokens=6,
                                          greedy=True)
        sampled, sm = build().generate(prompt, max_new_tokens=6,
                                       greedy=False, temperature=8.0)
        assert gm.tokens == 6 and sm.tokens == 6
        assert all(0 <= t < cfg.vocab_size for t in sampled)
        assert list(sampled) != list(greedy_out)   # pre-fix: identical

    def test_sample_token_temperature_law(self):
        """Low temperature concentrates on the argmax; high temperature
        spreads — and every draw comes off the supplied RNG."""
        logits = np.zeros(32)
        logits[7] = 4.0
        cold = {sample_token(logits, np.random.default_rng(0), 0.05)
                for _ in range(50)}
        assert cold == {7}
        rng = np.random.default_rng(0)
        hot = [sample_token(logits, rng, 4.0) for _ in range(300)]
        assert 7 in hot
        assert any(t != 7 for t in hot)
        # determinism per seed: the testbed RNG is the only entropy
        rng2 = np.random.default_rng(0)
        assert hot == [sample_token(logits, rng2, 4.0)
                       for _ in range(300)]

    def test_windowed_serving_with_relay_plane(self, tiny):
        """run_queue serves correctly off a relay-enabled gossip seeker
        and surfaces relay totals in ServeMetrics."""
        cfg, model, params = tiny
        gcfg = GTRACConfig(gossip_enabled=True, relay_enabled=True,
                           gossip_seekers=4, anchor_shards=4,
                           gossip_fanout=2, relay_fanout=2)
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, gcfg=gcfg,
                                  seed=0)
        for _ in range(2):
            srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=3))
        done = srv.run_queue()
        assert all(len(r.output) == 3 for r in done)
        assert srv.gossip.relay is not None
        assert srv.gossip.relay.stats.rounds >= 1
        assert done[0].metrics.relay_msgs > 0
        assert done[0].metrics.relay_bytes > 0

    def test_repair_preserves_correct_output(self, tiny):
        """A repaired (swapped) chain must still compute the right tokens —
        stateless hops make repair semantically transparent."""
        cfg, model, params = tiny
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"honeypot": 2, "golden": 2},
                                  algorithm="gtrac", seed=5)
        want = monolithic_greedy(cfg, model, params, np.arange(1, 9), 4)
        for rid in range(8):
            out, met = srv.generate(np.arange(1, 9), max_new_tokens=4,
                                    request_id=rid)
            if met.tokens == 4:
                assert list(out) == want


# run_queue's sim clock per stream, as served by the loop that read each
# token from the device before the next stream's hops (commit f9738a3):
# (prompt length, emit_ms, token_latency_ms). Same fleet seed, prompts and
# budgets as ``_window_run``; reading tokens once per window changes no draw.
PARENT_SIM_STAMPS = {
    "gpt2": [
        (5, [1094.5464360926412, 10324.852815965578, 11540.893123513491,
             11901.17570316153, 12211.139511309648, 12498.731942188731],
         [1094.5464360926412, 927.6921711092938, 289.3243264220334,
          319.28314773051864, 309.9638081481178, 287.5924308790835]),
        (9, [1968.836659917907, 10788.073790788163, 11559.46516427315],
         [1968.836659917907, 1390.913145931879, 307.89636718169027]),
        (13, [9397.160644856283, 11251.568797091459, 11543.589142508785,
              11899.828534310565, 12200.317154108008],
         [9397.160644856283, 1854.4081522351744, 292.02034541732576,
          317.9359788795528, 299.1414509464771]),
        (7, [1747.2404751106624, 10353.166094819997, 11581.892555431014,
             11885.523446460531],
         [1747.2404751106624, 956.0054499637145, 330.32375833955393,
          303.6308910295187]),
    ],
    "zamba2": [
        (29, [10137.56599104846, 13630.67240292372, 14018.979342220291,
              14480.834471080472, 14875.172762124592],
         [10137.56599104846, 426.52016985539046, 388.30693929657207,
          412.0663288757936, 394.33829104411916]),
        (31, [11171.552579874744, 13580.622740513452, 14068.768142204679],
         [11171.552579874744, 376.47050744512273, 438.0957392809588]),
        (40, [13204.15223306833, 13604.706114354978, 14054.282595226337,
              14466.53010129137],
         [13204.15223306833, 400.55388128664754, 423.6101923026181,
          397.7619590866899]),
    ],
}


def _window_model(family):
    """A test-width model of ``family`` with its prompts and budgets. The
    GPT-2 layers are scaled up so greedy decoding does not just repeat a
    token; Zamba2's prompts straddle its 32-position stage bucket."""
    if family == "gpt2":
        cfg = get_config("gpt2-large").reduced(num_layers=4, vocab_size=128,
                                               remat=False)
        params = build_model(cfg).init(KEY)
        params = {**params, "layers": jax.tree.map(lambda a: a * 8,
                                                   params["layers"])}
        lens, budgets = (5, 9, 13, 7), (6, 3, 5, 4)
    else:
        cfg = get_config("zamba2-7b").reduced(num_layers=6,
                                              hybrid_layer_ids=(2, 5))
        params = build_model(cfg).init(KEY)
        lens, budgets = (29, 31, 40), (5, 3, 4)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    return cfg, params, prompts, budgets


def _window_run(cfg, params, prompts, budgets, on_window=None):
    """``run_queue`` over streams of different prompt and output lengths
    on a fleet that repairs some hops; returns the server and streams."""
    srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                              replicas={"honeypot": 1, "turtle": 1,
                                        "golden": 2}, seed=3)
    if on_window is not None:
        on_window(srv)
    reqs = [srv.submit(SubmitSpec(prompt=p, max_new_tokens=n))
            for p, n in zip(prompts, budgets)]
    srv.run_queue()
    return srv, reqs


@pytest.mark.parametrize("family", ["gpt2", "zamba2"])
class TestOneTokenReadPerWindow:
    def test_serves_generates_tokens_and_the_parents_sim_clock(self,
                                                                family):
        cfg, params, prompts, budgets = _window_model(family)
        _, reqs = _window_run(cfg, params, prompts, budgets)
        ref = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, seed=0)
        want = PARENT_SIM_STAMPS[family]
        assert len(reqs) == len(want)
        for req, (n, emit_ms, latency_ms) in zip(reqs, want):
            out, met = ref.generate(req.prompt, max_new_tokens=len(emit_ms))
            assert len(req.prompt) == n
            assert req.output == [int(t) for t in out]
            assert met.tokens == len(req.output) == req.max_new_tokens
            assert req.metrics.emit_ms == pytest.approx(emit_ms, rel=1e-12)
            assert req.metrics.token_latency_ms == pytest.approx(
                latency_ms, rel=1e-12)

    def test_one_read_per_window_after_its_last_execute(self, family,
                                                        monkeypatch):
        """Token ids cross to the host once per decode window, after every
        chain of the window was dispatched; ``_emit_token`` sees Python
        ints; no hop reads a device array (the padded family pads the
        host ids)."""
        from jax._src.array import ArrayImpl

        cfg, params, prompts, budgets = _window_model(family)
        log = []                            # "window" | "execute" | "emit"
        in_hop, hop_reads = [False], []
        for name in ("__array__", "__buffer__"):   # numpy's two ways in
            def counted(self, *a, _read=getattr(ArrayImpl, name), **kw):
                if in_hop[0]:
                    hop_reads.append(self.shape)
                return _read(self, *a, **kw)

            monkeypatch.setattr(ArrayImpl, name, counted)
        value = ArrayImpl._value              # int(), .item(), ...
        monkeypatch.setattr(ArrayImpl, "_value", property(
            lambda self: counted(self, _read=lambda a: value.fget(a))))

        def hooks(srv):
            nxt = srv.admission.next_window

            def next_window(*a, **kw):
                log.append("window")
                return nxt(*a, **kw)

            srv.admission.next_window = next_window
            emit = srv._emit_token

            def emit_token(req, tok, t_emit):
                assert type(tok) is int
                log.append("emit")
                emit(req, tok, t_emit)

            srv._emit_token = emit_token

            def hop_of(fn):
                def hop(payload):
                    in_hop[0] = True
                    try:
                        return fn(payload)
                    finally:
                        in_hop[0] = False
                return hop

            srv.stage_fns = [hop_of(fn) for fn in srv.stage_fns]
            submit = srv.submit

            def submit_logged(spec):
                req = submit(spec)
                run = req.executor.execute

                def execute(*a, **kw):
                    log.append("execute")
                    return run(*a, **kw)

                req.executor.execute = execute
                return req

            srv.submit = submit_logged

        srv, reqs = _window_run(cfg, params, prompts, budgets, hooks)
        assert hop_reads == []
        windows, cur = [], None
        for ev in log:
            if ev == "window":
                cur = []
                windows.append(cur)
            else:
                cur.append(ev)
        emitting = [w for w in windows if "emit" in w]
        for w in emitting:              # every emit after the last execute
            first_emit = w.index("emit")
            assert "execute" not in w[first_emit:]
        snap = srv.obs.snapshot()
        tokens = sum(len(r.output) for r in reqs)
        assert snap["serve/token_reads"] == len(emitting) < tokens
        assert snap["serve/tokens_read"] == tokens == log.count("emit")
        # a full window reads as many tokens as it has streams
        assert max(w.count("emit") for w in emitting) == len(reqs)


class TestSubmitSpecAPI:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            SubmitSpec(prompt=np.arange(4), kind="bogus")
        with pytest.raises(ValueError):
            SubmitSpec(prompt=np.arange(4), max_new_tokens=0)
        spec = SubmitSpec(prompt=[1, 2, 3])
        assert spec.prompt.dtype == np.int32 and spec.kind == "auto"

    def test_engine_shim_warns_and_behaves(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(cfg, params)
        with pytest.deprecated_call():
            req = eng.submit(np.arange(1, 5), max_new_tokens=2)
        assert req.max_new_tokens == 2 and req.request_id == 0

    def test_server_shim_warns(self, tiny):
        cfg, model, params = tiny
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, seed=0)
        with pytest.deprecated_call():
            req = srv.submit(np.arange(1, 5), max_new_tokens=2)
        assert req.request_id == 10_000

    def test_pinned_request_id_advances_counter(self, tiny):
        cfg, model, params = tiny
        eng = ServingEngine(cfg, params)
        a = eng.submit(SubmitSpec(prompt=np.arange(4)))
        b = eng.submit(SubmitSpec(prompt=np.arange(4), request_id=7))
        c = eng.submit(SubmitSpec(prompt=np.arange(4)))
        assert (a.request_id, b.request_id, c.request_id) == (0, 7, 8)


class TestDisaggregatedServing:
    def test_long_prompt_chunked_prefill_matches_monolithic(self, tiny):
        """A stream prefilled in dedicated chunks must emit exactly the
        tokens monolithic greedy decoding would — chunking and warm
        promotion change scheduling, never semantics."""
        cfg, model, params = tiny
        gcfg = GTRACConfig(disaggregate=True, prefill_chunk_tokens=8,
                           kv_reuse_bonus=0.25)
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, gcfg=gcfg, seed=0)
        long_p, short_p = np.arange(1, 25), np.arange(1, 7)
        r1 = srv.submit(SubmitSpec(prompt=long_p, max_new_tokens=4))
        r2 = srv.submit(SubmitSpec(prompt=short_p, max_new_tokens=4))
        done = srv.run_queue()
        assert len(done) == 2
        assert r1.output == monolithic_greedy(cfg, model, params, long_p, 4)
        assert r2.output == monolithic_greedy(cfg, model, params, short_p, 4)
        assert r1.metrics.prefill_chunks == 3        # 24 tokens / 8
        assert r1.metrics.prefill_tokens == 24
        assert r2.metrics.prefill_chunks == 0        # inline prefill
        # emission accounting: TTFT set, stamps nondecreasing, and the
        # short stream reaches its first token before the chunked one
        for r in (r1, r2):
            assert r.metrics.ttft_ms > 0 and len(r.metrics.emit_ms) == 4
            assert all(b >= a for a, b in zip(r.metrics.emit_ms,
                                              r.metrics.emit_ms[1:]))
        assert r2.metrics.ttft_ms < r1.metrics.ttft_ms
        # warm handoff: the promoted stream decodes on its warm chain
        assert r1.metrics.kv_warm_hits >= 1

    def test_multi_token_charges_never_poison_latency_ema(self, tiny):
        """The anchor's latency_est_ms means ONE decode step. Prefill
        chunks and cold recomputes are charged multi-token wall latency,
        but the report fed to the EMA must be rescaled to its
        single-token equivalent — unnormalized, a 8-token chunk makes
        its peers look ~8x slow, routing flees to the cold replica, and
        chains ping-pong (each flip a full-prefix recompute)."""
        cfg, model, params = tiny
        gcfg = GTRACConfig(disaggregate=True, prefill_chunk_tokens=8,
                           kv_reuse_bonus=0.25)
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, gcfg=gcfg, seed=0)
        srv.submit(SubmitSpec(prompt=np.arange(1, 25), max_new_tokens=4))
        srv.run_queue()
        table = srv.bed.anchor.snapshot(srv.bed.now)
        for pid, est in zip(table.peer_ids, table.latency_ms):
            peer = srv.bed.peers[int(pid)]
            one_tok = peer.compute_ms(1) + peer.net_delay_ms
            # EMA stays in single-token units (jitter sigma is 0.1; an
            # unnormalized 8-token chunk would land near 8x one_tok)
            assert est < 2.0 * one_tok
        assert not srv._tok_scale               # every charge consumed

    def test_explicit_kind_overrides_bucket(self, tiny):
        cfg, model, params = tiny
        gcfg = GTRACConfig(disaggregate=True, prefill_chunk_tokens=8)
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  replicas={"golden": 2}, gcfg=gcfg, seed=0)
        pinned_pre = srv.submit(SubmitSpec(prompt=np.arange(1, 7),
                                           max_new_tokens=2, kind="prefill"))
        pinned_dec = srv.submit(SubmitSpec(prompt=np.arange(1, 25),
                                           max_new_tokens=2, kind="decode"))
        srv.run_queue()
        assert pinned_pre.metrics.prefill_chunks >= 1
        assert pinned_dec.metrics.prefill_chunks == 0
        assert len(pinned_pre.output) == 2 and len(pinned_dec.output) == 2
