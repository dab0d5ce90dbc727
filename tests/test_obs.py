"""Observability plane (repro.obs): tracer/metrics units, exact
FakeClock span trees across the rpc boundary, executor failover/hedge
markers, export round-trips, and the traced serving integration with
its TTFT decomposition identity; the served path's host-clock spans
and their profiler annotations."""
import gc
import glob
import itertools
import json

import numpy as np
import pytest

from repro.configs.base import GTRACConfig
from repro.control_plane import (
    FakeClock,
    LoopbackTransport,
    RpcChannel,
    RpcPolicy,
    RpcTimeout,
    ShardHost,
)
from repro.core.hedging import HedgedChainExecutor
from repro.obs.export import (
    export_chrome,
    export_jsonl,
    load_jsonl,
    validate_jsonl,
)
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    percentiles,
)
from repro.obs.report import itl_breakdown, plan_wall_summary, ttft_breakdown
from repro.obs.trace import (
    HOST_DOMAIN,
    NOOP_TRACER,
    Span,
    TraceBuffer,
    Tracer,
)


@pytest.fixture
def gcfg():
    return GTRACConfig()


# ---------------------------------------------------------------------------
# metrics: the shared percentile helper + registry views
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_percentiles_empty_sentinel(self):
        assert percentiles([], (50, 99)) == (-1.0, -1.0)

    def test_percentiles_values(self):
        xs = list(range(1, 101))
        p50, p90 = percentiles(xs, (50, 90))
        assert p50 == pytest.approx(np.percentile(xs, 50))
        assert p90 == pytest.approx(np.percentile(xs, 90))

    def test_counter_gauge(self):
        reg = MetricsRegistry()
        reg.counter("a/hits").inc()
        reg.counter("a/hits").inc(2)      # get-or-create returns same
        reg.gauge("a/level").set(7.5)
        snap = reg.snapshot()
        assert snap["a/hits"] == 3
        assert snap["a/level"] == 7.5

    def test_histogram_buckets_and_stats(self):
        h = Histogram(uppers=(1, 10, 100))
        for v in (0.5, 5, 50, 500):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(555.5)
        assert h.counts == [1, 1, 1, 1]   # one in overflow
        assert h.mean() == pytest.approx(555.5 / 4)
        assert h.percentile(50) == 10     # bucket upper bound
        assert h.percentile(99) == 500    # overflow reports max
        assert Histogram((1,)).percentile(50) == -1.0

    def test_expose_is_live_view(self):
        from repro.sync.relay import RelayStats
        reg = MetricsRegistry()
        rs = RelayStats()
        reg.expose("relay", rs)
        reg.derived("relay/wire_bytes", rs.seeker_wire_bytes)
        assert reg.snapshot()["relay/msgs"] == 0
        rs.msgs += 5
        rs.msg_bytes += 420
        snap = reg.snapshot()              # fresh read, no re-expose
        assert snap["relay/msgs"] == 5
        assert snap["relay/wire_bytes"] == rs.seeker_wire_bytes()
        assert isinstance(snap["relay/msgs"], int)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def _tracer(self, t0=0.0):
        state = {"t": t0}
        tr = Tracer(TraceBuffer(), clock=lambda: state["t"])
        return tr, state

    def test_lexical_nesting(self):
        tr, st = self._tracer()
        with tr.span("outer"):
            st["t"] = 1.0
            with tr.span("inner"):
                st["t"] = 3.0
            st["t"] = 5.0
        spans = {s.name: s for s in tr.sink.spans}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["outer"].parent_id is None
        assert spans["inner"].dur_s == pytest.approx(2.0)
        assert spans["outer"].dur_s == pytest.approx(5.0)

    def test_non_lexical_request_span(self):
        tr, st = self._tracer()
        req = tr.begin("request", t0=0.0, rid=1)
        with tr.span("window"):
            st["t"] = 2.0
        tr.end(req, t1=4.0, ttft_ms=123.0)
        spans = {s.name: s for s in tr.sink.spans}
        # the window was pushed while the request span was NOT on the
        # stack, so it does not become the request's child
        assert spans["window"].parent_id is None
        assert spans["request"].dur_s == pytest.approx(4.0)
        assert spans["request"].attrs["ttft_ms"] == 123.0

    def test_add_and_event_post_hoc(self):
        tr, _ = self._tracer()
        p = tr.add("step", 1.0, 2.5, rid=9)
        tr.add("hop", 1.0, 1.5, parent=p, peer=3)
        tr.event("marker", t=2.0, parent=p)
        hop = [s for s in tr.sink.spans if s.name == "hop"][0]
        mk = [s for s in tr.sink.spans if s.name == "marker"][0]
        assert hop.parent_id == p.span_id
        assert hop.dur_s == pytest.approx(0.5)
        assert mk.dur_s == 0.0 and mk.t0 == 2.0

    def test_scope_shares_ring_separate_domain(self):
        tr, _ = self._tracer()
        rpc = tr.scope("rpc", clock=lambda: 42.0)
        sp = rpc.begin("rpc.collect")
        rpc.end(sp)
        assert sp.domain == "rpc" and sp.t0 == 42.0
        assert sp in tr.sink.spans          # same buffer

    def test_buffer_eviction_counts(self):
        buf = TraceBuffer(capacity=2)
        tr = Tracer(buf, clock=lambda: 0.0)
        for i in range(5):
            tr.end(tr.begin(f"s{i}"))
        assert len(buf) == 2 and buf.dropped == 3

    def test_noop_tracer_is_inert(self):
        sp = NOOP_TRACER.begin("x", anything=1)
        assert NOOP_TRACER.span("y") is sp        # one shared object
        assert NOOP_TRACER.add("z", 0, 1) is sp
        assert not NOOP_TRACER.enabled
        with NOOP_TRACER.span("w"):
            pass                                   # context form works


# ---------------------------------------------------------------------------
# exact rpc span trees on FakeClock (cross-process stamps included)
# ---------------------------------------------------------------------------


class _DropTransport(LoopbackTransport):
    """Loopback that eats the next n replies AFTER servicing them."""

    def __init__(self, host):
        super().__init__(host)
        self.mute = False
        self.drop_next = 0

    def post(self, msg):
        if self.mute:
            return
        super().post(msg)
        if self.drop_next > 0 and self._out:
            self._out.pop()
            self.drop_next -= 1


class TestRpcSpanTree:
    POL = RpcPolicy(timeout_s=1.0, retries=2, backoff_base_s=0.05,
                    backoff_factor=2.0)

    def _channel(self, gcfg, svc_ticks=None):
        clock = FakeClock()
        host = ShardHost(gcfg, 0, svc_clock=(
            (lambda it: (lambda: next(it)))(iter(svc_ticks))
            if svc_ticks is not None else None))
        tr = _DropTransport(host)
        ch = RpcChannel(tr, self.POL, clock)
        ch.tracer = Tracer(TraceBuffer(), clock=clock.monotonic,
                           domain="rpc")
        return ch, tr, clock

    def test_retry_with_backoff_exact_tree(self, gcfg):
        """Lost reply -> one backoff, one retry answered from the worker
        dedup cache carrying the ORIGINAL cross-process span stamp. The
        whole tree — ids, parents, t0/t1 — is exact on FakeClock."""
        ch, tr, clock = self._channel(gcfg, svc_ticks=[10.0, 10.007])
        tr.drop_next = 1
        ch.request("register", 7, 0, 2, 0.0, "", None, None, 0, None)
        assert ch.stats.rpc_retries == 1
        assert clock.sleeps == [0.05]
        spans = list(ch.tracer.sink.spans)   # completion order
        names = [s.name for s in spans]
        assert names == ["rpc.attempt", "rpc.backoff", "rpc.attempt",
                         "rpc.worker", "rpc.collect"]
        att0, bo, att1, wrk, root = spans
        assert root.parent_id is None
        assert att0.parent_id == bo.parent_id == att1.parent_id \
            == root.span_id
        assert wrk.parent_id == att1.span_id
        # FakeClock never advances inside a poll, so the failed attempt
        # is instantaneous and the backoff is the only elapsed time
        assert (att0.t0, att0.t1) == (0.0, 0.0)
        assert att0.attrs == {"attempt": 0, "ok": False, "timeout": True}
        assert (bo.t0, bo.t1) == (0.0, 0.05)
        assert (att1.t0, att1.t1) == (0.05, 0.05)
        assert att1.attrs == {"attempt": 1, "ok": True}
        # worker span: service time measured by the injected worker
        # clock (10.007 - 10.0), laid back-to-back against attempt end
        assert wrk.t1 == 0.05
        assert wrk.dur_s == pytest.approx(0.007)
        assert wrk.attrs == {"worker_span": 1}
        assert root.attrs["outcome"] == "ok"
        assert root.attrs["attempts"] == 2
        assert root.attrs["op"] == "register"
        assert (root.t0, root.t1) == (0.0, 0.05)

    def test_timeout_exhaustion_tree(self, gcfg):
        """Dead-air worker: retries+1 zero-length attempts separated by
        exact exponential backoffs; the root records the outcome."""
        ch, tr, clock = self._channel(gcfg)
        tr.mute = True
        with pytest.raises(RpcTimeout):
            ch.request("ping")
        spans = list(ch.tracer.sink.spans)
        names = [s.name for s in spans]
        assert names == ["rpc.attempt", "rpc.backoff", "rpc.attempt",
                         "rpc.backoff", "rpc.attempt", "rpc.collect"]
        backoffs = [s for s in spans if s.name == "rpc.backoff"]
        assert [pytest.approx(b.dur_s) for b in backoffs] == [0.05, 0.10]
        assert backoffs[1].t0 == pytest.approx(0.05)
        root = spans[-1]
        assert root.attrs["outcome"] == "timeout"
        assert root.attrs["attempts"] == 3
        assert root.t1 == pytest.approx(0.15)
        assert all(s.name != "rpc.worker" for s in spans)

    def test_untraced_channel_no_spans(self, gcfg):
        clock = FakeClock()
        ch = RpcChannel(LoopbackTransport(ShardHost(gcfg, 0)), self.POL,
                        clock)
        ch.request("ping")
        assert ch.tracer is NOOP_TRACER


# ---------------------------------------------------------------------------
# executor markers: failover splice + hedged race
# ---------------------------------------------------------------------------


def _stage_table(gcfg, latencies):
    from repro.core.registry import AnchorRegistry
    a = AnchorRegistry(gcfg)
    for pid, lat in enumerate(latencies):
        a.register(pid, 0, 3, now=0.0, latency_ms=lat)
        a.heartbeat(pid, 0.0)
    a.register(99, 3, 6, now=0.0, latency_ms=50.0)
    a.heartbeat(99, 0.0)
    return a.snapshot(0.0)


class TestExecutorMarkers:
    def test_failover_splice_event(self, gcfg):
        from repro.core.executor import ChainExecutor
        t = _stage_table(gcfg, [100.0, 100.0])

        def hop(pid, k, payload):
            return payload, 150.0, pid != 0     # peer 0 fails

        ex = ChainExecutor(gcfg, hop)
        ex.tracer = Tracer(TraceBuffer(), clock=lambda: 7.0)
        report, _ = ex.execute([0, 99], t)
        assert report.success and report.repaired
        ev = [s for s in ex.tracer.sink.spans
              if s.name == "failover.splice"]
        assert len(ev) == 1
        assert ev[0].cat == "failover" and ev[0].dur_s == 0.0
        assert ev[0].t0 == 7.0
        assert ev[0].attrs["failed_peer"] == 0
        assert ev[0].attrs["repair_peer"] == report.repair_peer == 1
        assert ev[0].attrs["via"] == "search"    # no RoutePlan given
        assert ev[0].attrs["stage"] == 0

    def test_hedge_fired_and_won_events(self, gcfg):
        t = _stage_table(gcfg, [100.0, 100.0])
        lat = {0: 1000.0, 1: 80.0, 99: 50.0}     # peer 0 straggles

        def hop(pid, k, payload):
            return payload, lat[pid], True

        ex = HedgedChainExecutor(gcfg, hop, quantile_factor=2.0)
        ex.tracer = Tracer(TraceBuffer(), clock=lambda: 3.0)
        report, _ = ex.execute([0, 99], t)
        assert report.success
        ev = {s.name: s for s in ex.tracer.sink.spans}
        assert set(ev) == {"hedge.fired", "hedge.won"}
        fired, won = ev["hedge.fired"], ev["hedge.won"]
        assert fired.attrs == {"stage": 0, "peer": 0, "hedge_peer": 1,
                               "trigger_ms": 200.0}
        # winner total = trigger(200) + backup(80); saved = 1000 - 280
        assert won.attrs["saved_ms"] == pytest.approx(720.0)
        assert won.attrs["hedge_peer"] == 1

    def test_no_hedge_no_events(self, gcfg):
        t = _stage_table(gcfg, [100.0, 100.0])

        def hop(pid, k, payload):
            return payload, 90.0, True

        ex = HedgedChainExecutor(gcfg, hop)
        ex.tracer = Tracer(TraceBuffer(), clock=lambda: 0.0)
        report, _ = ex.execute([0, 99], t)
        assert report.success
        assert len(ex.tracer.sink.spans) == 0


# ---------------------------------------------------------------------------
# export: jsonl round-trip, schema validation, chrome events
# ---------------------------------------------------------------------------


def _demo_buffer():
    st = {"t": 0.0}
    tr = Tracer(TraceBuffer(), clock=lambda: st["t"], domain="serve")
    req = tr.begin("request", cat="request", t0=0.0, rid=1)
    tr.add("decode.step", 0.0, 0.25, cat="decode", parent=req, rid=1,
           emitted=True, first_token=True)
    tr.scope("rpc", clock=lambda: 9.0).end(
        tr.scope("rpc").begin("rpc.collect", cat="rpc", t0=9.0), t1=9.5)
    st["t"] = 0.25
    tr.end(req, ttft_ms=250.0)
    return tr.sink


class TestExport:
    def test_jsonl_round_trip_and_validate(self, tmp_path):
        buf = _demo_buffer()
        path = str(tmp_path / "t.jsonl")
        export_jsonl(buf, path)
        n, errors = validate_jsonl(path)
        assert n == len(buf) and errors == []
        rows = load_jsonl(path)
        by_name = {r["name"]: r for r in rows}
        assert by_name["decode.step"]["parent"] == \
            by_name["request"]["id"]
        assert by_name["decode.step"]["dur_ms"] == pytest.approx(250.0)
        assert by_name["request"]["attrs"]["ttft_ms"] == 250.0
        assert by_name["rpc.collect"]["domain"] == "rpc"

    def test_validator_catches_corruption(self, tmp_path):
        buf = _demo_buffer()
        path = str(tmp_path / "bad.jsonl")
        export_jsonl(buf, path)
        rows = [json.loads(line) for line in open(path)]
        rows[0]["t1"] = rows[0]["t0"] - 1.0       # negative duration
        del rows[1]["name"]                       # missing key
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        _, errors = validate_jsonl(path)
        assert len(errors) >= 2

    def test_chrome_export_structure(self, tmp_path):
        buf = _demo_buffer()
        path = str(tmp_path / "t.trace.json")
        export_chrome(buf, path)
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs if e["ph"] == "X"}
        assert len(pids) == 2                     # serve + rpc domains
        step = [e for e in evs if e.get("name") == "decode.step"][0]
        assert step["dur"] == pytest.approx(250.0 * 1e3)  # microseconds
        assert any(e["ph"] == "M" for e in evs)   # process_name metadata


# ---------------------------------------------------------------------------
# report: decomposition identities on synthetic spans
# ---------------------------------------------------------------------------


class TestReport:
    def test_ttft_breakdown_sums(self):
        tr = Tracer(TraceBuffer(), clock=lambda: 0.0, domain="serve")
        req = tr.begin("request", cat="request", t0=0.0, rid=5)
        tr.add("queue.wait", 0.0, 0.1, cat="serve", parent=req)
        c = tr.add("prefill.chunk", 0.1, 0.3, cat="prefill", parent=req,
                   ok=True)
        tr.add("hop", 0.1, 0.3, cat="exec", parent=c, peer=1, ok=True)
        tr.add("prefill.stall", 0.3, 0.35, cat="prefill", parent=req)
        s = tr.add("decode.step", 0.35, 0.5, cat="decode", parent=req,
                   rid=5, emitted=True, first_token=True)
        tr.add("hop", 0.35, 0.45, cat="exec", parent=s, peer=2, ok=False)
        tr.add("hop", 0.45, 0.5, cat="exec", parent=s, peer=3, ok=True)
        tr.end(req, t1=0.5, ttft_ms=500.0, stale_rounds_max=2)
        (row,) = ttft_breakdown(tr.sink)
        assert row["rid"] == 5 and row["complete"]
        assert row["queue_wait_ms"] == pytest.approx(100.0)
        assert row["prefill_ms"] == pytest.approx(200.0)
        assert row["prefill_stall_ms"] == pytest.approx(50.0)
        assert row["decode_ms"] == pytest.approx(150.0)
        assert row["failover_ms"] == pytest.approx(100.0)  # failed hop
        assert row["stale_rounds_max"] == 2
        assert row["ttft_sum_ms"] == pytest.approx(row["measured_ttft_ms"])

    def test_itl_breakdown_exec_plus_drag(self):
        tr = Tracer(TraceBuffer(), clock=lambda: 0.0, domain="serve")
        req = tr.begin("request", cat="request", t0=0.0, rid=1)
        tr.add("decode.step", 0.0, 0.1, parent=req, cat="decode", rid=1,
               emitted=True, first_token=True, drag_ms=100.0)
        tr.add("decode.step", 0.2, 0.25, parent=req, cat="decode", rid=1,
               emitted=True, first_token=False, drag_ms=0.0)
        tr.end(req, t1=0.25, ttft_ms=100.0)
        out = itl_breakdown(tr.sink)
        assert out["n"] == 1
        # ITL = own exec (50ms) + PREVIOUS step's window drag (100ms)
        assert out["itl_p50_ms"] == pytest.approx(150.0)
        assert out["exec_p50_ms"] == pytest.approx(50.0)
        assert out["drag_p50_ms"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# traced serving integration (real model, sim clock)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    cfg = get_config("gpt2-large").reduced(num_layers=4, vocab_size=128,
                                           remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    return cfg, params


def _traced_server(tiny_model, **gkw):
    from repro.serving.gtrac_serve import GTRACPipelineServer
    cfg, params = tiny_model
    gcfg = GTRACConfig(trace_enabled=True, **gkw)
    return GTRACPipelineServer(cfg, params, layers_per_stage=2,
                               gcfg=gcfg, seed=3)


class TestTracedServing:
    def test_ttft_identity_and_completion(self, tiny_model, tmp_path):
        """End-to-end: every completed stream's critical-path components
        sum EXACTLY to its measured TTFT, the exported trace passes the
        schema check, and the summary carries completion accounting."""
        from repro.serving.api import SubmitSpec
        from repro.serving.gtrac_serve import latency_summary
        srv = _traced_server(tiny_model, gossip_enabled=True,
                             relay_enabled=True, gossip_seekers=3,
                             disaggregate=True, prefill_chunk_tokens=4)
        for i in range(4):
            srv.submit(SubmitSpec(prompt=np.arange(1, 9 + 4 * i),
                                  max_new_tokens=4,
                                  arrival_time=0.01 * i))
        done = srv.run_queue()
        rows = ttft_breakdown(srv.trace)
        assert len(rows) == 4
        completed = [r for r in rows if r["complete"]]
        assert completed
        for r in completed:
            assert r["ttft_sum_ms"] == pytest.approx(
                r["measured_ttft_ms"], abs=1e-6), r
        # measured_ttft on the span tree == the stream's metrics ttft
        by_rid = {r.request_id: r for r in done}
        for r in completed:
            assert r["measured_ttft_ms"] == pytest.approx(
                by_rid[r["rid"]].metrics.ttft_ms)
        ls = latency_summary(done)
        assert ls["requests"] == 4
        assert ls["completed"] + ls["incomplete"] == 4
        assert ls["completion_rate"] == pytest.approx(
            ls["completed"] / 4)
        path = str(tmp_path / "serve.jsonl")
        export_jsonl(srv.trace, path)
        n, errors = validate_jsonl(path)
        assert n == len(srv.trace) and errors == []

    def test_stream_metrics_fill_matches_layer_stats(self, tiny_model):
        """Satellite regression: the registry-backed fill reproduces the
        exact values the old hand-written mirrors copied."""
        from repro.serving.api import SubmitSpec
        srv = _traced_server(tiny_model, gossip_enabled=True,
                             relay_enabled=True, gossip_seekers=3)
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=3))
        (req,) = srv.run_queue()
        rs = srv.gossip.relay.stats
        m = req.metrics
        assert m.relay_msgs == rs.msgs + rs.summaries
        assert m.relay_bytes == rs.seeker_wire_bytes()
        assert m.relay_duplicates == rs.duplicates
        assert m.relay_digest_mismatches == rs.digest_mismatches
        assert m.relay_rejected_chains == rs.rejected_chains
        assert m.relay_quarantines == rs.quarantines
        assert isinstance(m.relay_msgs, int)
        # no process control plane wired -> fields keep their defaults
        assert m.shard_rpc_retries == 0 and m.worker_restarts == 0

    def test_disabled_tracing_is_noop(self, tiny_model):
        from repro.serving.api import SubmitSpec
        from repro.serving.gtrac_serve import GTRACPipelineServer
        cfg, params = tiny_model
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                  gcfg=GTRACConfig(), seed=3)
        assert srv.trace is None and srv.tracer is NOOP_TRACER
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=2))
        (req,) = srv.run_queue()
        assert req.metrics.tokens == 2
        assert srv.router.tracer is NOOP_TRACER

    def test_generate_path_traced(self, tiny_model):
        """The per-token generate() loop also carries request/step/hop
        spans, and the first step IS the TTFT (no queue, no windows)."""
        srv = _traced_server(tiny_model)
        out, met = srv.generate(np.arange(1, 9), max_new_tokens=3,
                                request_id=77)
        assert met.tokens == 3
        (row,) = ttft_breakdown(srv.trace)
        assert row["rid"] == 77 and row["complete"]
        assert row["ttft_sum_ms"] == pytest.approx(
            row["measured_ttft_ms"], abs=1e-6)
        assert row["measured_ttft_ms"] == pytest.approx(met.ttft_ms)
        steps = [s for s in srv.trace.spans if s.name == "decode.step"]
        assert len(steps) == 3
        hops = [s for s in srv.trace.spans if s.name == "hop"]
        by_id = {s.span_id: s for s in srv.trace.spans}
        for h in hops:                       # hops tile their step
            assert by_id[h.parent_id].name == "decode.step"
        for st in steps:
            tiled = sum(h.dur_s for h in hops
                        if h.parent_id == st.span_id)
            assert tiled == pytest.approx(st.dur_s)


# ---------------------------------------------------------------------------
# host-clock spans of the served path (host domain, profiler annotations)
# ---------------------------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    """Replace ``jax.profiler.TraceAnnotation`` with a recorder of every
    annotation made, entered and exited."""
    import jax
    log = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name
            log.append(("new", name))

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


def _counting_clock():
    """An injected host clock: 0, 1, 2, ... one tick per read."""
    ticks = itertools.count()
    return lambda: float(next(ticks))


def _golden_server(tiny_model, **gkw):
    """Fault-free fleet: every hop dispatches its stage."""
    from repro.serving.gtrac_serve import GTRACPipelineServer
    cfg, params = tiny_model
    return GTRACPipelineServer(cfg, params, layers_per_stage=2,
                               replicas={"golden": 2},
                               gcfg=GTRACConfig(**gkw), seed=3)


def _tree(spans):
    """(name, children) tuples of the spans' forest, in start order."""
    kids = {}
    for sp in sorted(spans, key=lambda s: (s.t0, s.span_id)):
        kids.setdefault(sp.parent_id, []).append(sp)

    def walk(pid):
        return tuple((sp.name, walk(sp.span_id)) for sp in kids.get(pid, ()))

    return walk(None)


def _program_spans(buf):
    """The host domain's spans of the served path itself: without the
    ``gc`` and ``compile`` process spans, which land wherever the
    collector or the compiler happens to run."""
    return [sp for sp in buf.spans
            if sp.domain == HOST_DOMAIN and sp.cat != "process"]


class TestHostTracer:
    def test_annotations_enter_and_exit_lifo(self, annotations):
        tr = Tracer(TraceBuffer(), clock=_counting_clock(),
                    domain=HOST_DOMAIN, annotate=True)
        with tr.span("window"):
            with tr.span("admit"):
                pass
            execute = tr.begin("execute", push=True)
            tr.begin("hop.dispatch", push=True)
            tr.end(execute)               # out of order: closes the hop
            tr.add("gc", 0.0, 1.0)        # post-hoc: never annotated
            tr.event("marker")
        entered = [(k, n) for k, n in annotations if k != "new"]
        assert entered == [
            ("enter", "gtrac.window"), ("enter", "gtrac.admit"),
            ("exit", "gtrac.admit"), ("enter", "gtrac.execute"),
            ("enter", "gtrac.hop.dispatch"), ("exit", "gtrac.hop.dispatch"),
            ("exit", "gtrac.execute"), ("exit", "gtrac.window")]
        stack = []
        for kind, name in entered:
            if kind == "enter":
                stack.append(name)
            else:
                assert stack.pop() == name
        assert not stack and tr.current is None

    def test_tracer_without_annotate_enters_none(self, annotations):
        tr = Tracer(TraceBuffer(), clock=_counting_clock(),
                    domain=HOST_DOMAIN)
        with tr.span("window"):
            tr.end(tr.begin("execute", push=True))
        assert annotations == [] and len(tr.sink) == 2

    def test_disabled_host_tracer_records_and_annotates_nothing(
            self, tiny_model, annotations, monkeypatch):
        from repro.serving.api import SubmitSpec
        srv = _golden_server(tiny_model)
        assert srv.host_tracer is NOOP_TRACER
        assert srv.router.host_tracer is NOOP_TRACER
        made = []
        init = Span.__init__

        def counted(self, *a, **kw):
            made.append(a[2])
            init(self, *a, **kw)

        monkeypatch.setattr(Span, "__init__", counted)
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=2))
        (req,) = srv.run_queue()
        assert req.metrics.tokens == 2
        assert made == [] and annotations == []

    def test_exact_host_span_tree_of_run_queue(self, tiny_model):
        from repro.serving.api import SubmitSpec
        srv = _golden_server(tiny_model)
        tr = Tracer(TraceBuffer(), clock=_counting_clock(),
                    domain=HOST_DOMAIN)
        srv.set_host_tracer(tr)
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=2))
        (req,) = srv.run_queue()
        srv.set_host_tracer(None)
        assert req.metrics.tokens == 2
        spans = _program_spans(tr.sink)
        hops = (("hop.dispatch", ()), ("hop.dispatch", ()))
        tail = (("execute", hops), ("trust_fold", ()), ("kv", ()),
                ("token_pick", ()), ("token_sync", ()), ("emit", ()),
                ("finish", ()))
        first = (("admit", ()), ("sync_view", ()),
                 ("route", (("route.dp", ()),))) + tail
        # the second window routes on the unchanged snapshot and floor:
        # the router's window cache answers, so no DP runs
        second = (("admit", ()), ("sync_view", ()), ("route", ())) + tail
        assert _tree(spans) == (
            ("run_queue", (("window", first), ("window", second))),)
        by_id = {sp.span_id: sp for sp in spans}
        for sp in spans:                  # children nest in time too
            assert sp.t1 > sp.t0
            if sp.parent_id is not None:
                parent = by_id[sp.parent_id]
                assert parent.t0 < sp.t0 and sp.t1 < parent.t1
        assert [sp.attrs["stage"] for sp in spans
                if sp.name == "hop.dispatch"] == [0, 1, 0, 1]

    def test_one_dispatch_per_hop_and_one_sync_per_token(self, tiny_model):
        """Across chunked prefill, failing peers and repairs: a stage
        call for every hop that ran, a token picked on the device for
        every token the streams emitted, and one device-to-host read
        (``token_sync``) per window that picked any, after its last
        pick; the server's counters agree."""
        from repro.serving.api import SubmitSpec
        srv = _traced_server(tiny_model, disaggregate=True,
                             prefill_chunk_tokens=4)
        srv.set_host_tracer(srv.tracer.scope(HOST_DOMAIN,
                                             clock=_counting_clock()))
        for i in range(4):
            srv.submit(SubmitSpec(prompt=np.arange(1, 9 + 4 * i),
                                  max_new_tokens=4,
                                  arrival_time=0.01 * i))
        done = srv.run_queue()
        host = _program_spans(srv.trace)
        ran = [sp for sp in srv.trace.spans
               if sp.name == "hop" and sp.attrs["ok"]]
        failed = [sp for sp in srv.trace.spans
                  if sp.name == "hop" and not sp.attrs["ok"]]
        dispatched = [sp for sp in host if sp.name == "hop.dispatch"]
        assert ran and failed, "the fleet should both serve and fail"
        assert len(dispatched) == len(ran)
        picks = [sp for sp in host if sp.name == "token_pick"]
        syncs = [sp for sp in host if sp.name == "token_sync"]
        emitted = sum(r.metrics.tokens for r in done)
        assert len(picks) == emitted > len(syncs) > 0
        by_id = {sp.span_id: sp for sp in host}
        for sp in dispatched:
            assert by_id[sp.parent_id].name == "execute"
        for window in (sp for sp in host if sp.name == "window"):
            kids = [sp for sp in host if sp.parent_id == window.span_id]
            wpicks = [sp for sp in kids if sp.name == "token_pick"]
            wsyncs = [sp for sp in kids if sp.name == "token_sync"]
            assert len(wsyncs) == (1 if wpicks else 0)
            assert all(p.t1 <= s.t0 for p in wpicks for s in wsyncs)
        snap = srv.obs.snapshot()
        assert snap["serve/token_reads"] == len(syncs)
        assert snap["serve/tokens_read"] == emitted

    def test_gc_and_compile_spans_while_set(self):
        import jax
        import jax.numpy as jnp

        from repro.serving.gtrac_serve import hook_process_spans
        tr = Tracer(TraceBuffer(), domain=HOST_DOMAIN)
        unhook = hook_process_spans(tr)
        with tr.span("window"):
            gc.collect()
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0))
        unhook()
        by_name = {}
        for sp in tr.sink.spans:
            by_name.setdefault(sp.name, []).append(sp)
        (window,) = by_name["window"]
        full = [sp for sp in by_name["gc"] if sp.attrs["generation"] == 2]
        assert full and all(sp.cat == "process" for sp in by_name["gc"])
        assert isinstance(full[0].attrs["collected"], int)
        assert full[0].parent_id == window.span_id
        assert full[0].t0 <= full[0].t1
        compiles = by_name["compile"]      # the arange and the jit
        assert compiles
        for sp in compiles:
            assert window.t0 <= sp.t0 <= sp.t1 <= window.t1
        n = len(tr.sink)
        gc.collect()                      # removed: nothing more lands
        assert len(tr.sink) == n

    def test_set_host_tracer_hooks_and_clears(self, tiny_model):
        srv = _golden_server(tiny_model)
        hooks = len(gc.callbacks)
        tr = Tracer(TraceBuffer(), domain=HOST_DOMAIN)
        srv.set_host_tracer(tr)
        assert srv.host_tracer is tr and srv.router.host_tracer is tr
        assert len(gc.callbacks) == hooks + 1
        gc.collect()
        assert any(sp.name == "gc" for sp in tr.sink.spans)
        srv.set_host_tracer(None)
        assert srv.host_tracer is NOOP_TRACER
        assert srv.router.host_tracer is NOOP_TRACER
        assert len(gc.callbacks) == hooks

    def test_hooks_go_with_a_dropped_tracer(self, tiny_model):
        srv = _golden_server(tiny_model)
        hooks = len(gc.callbacks)
        srv.set_host_tracer(Tracer(TraceBuffer(), domain=HOST_DOMAIN))
        assert len(gc.callbacks) == hooks + 1
        del srv
        gc.collect()
        assert len(gc.callbacks) == hooks

    def test_traced_server_carries_serve_and_host_domains(self, tiny_model,
                                                          tmp_path):
        from repro.serving.api import SubmitSpec
        srv = _traced_server(tiny_model)
        assert srv.host_tracer.domain == HOST_DOMAIN
        assert srv.host_tracer.sink is srv.trace
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=3))
        srv.run_queue()
        domains = {sp.domain for sp in srv.trace.spans}
        assert {"serve", HOST_DOMAIN} <= domains
        routes = [sp for sp in srv.trace.spans
                  if sp.domain == HOST_DOMAIN and sp.name == "route"]
        plan = plan_wall_summary(srv.trace)
        assert plan["windows"] == len(routes) == 3
        assert plan["wall_us_total"] == pytest.approx(
            sum(sp.dur_s for sp in routes) * 1e6)
        assert all("wall_us" not in sp.attrs for sp in srv.trace.spans)
        path = str(tmp_path / "serve.trace.json")
        export_chrome(srv.trace, path)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        rows = {e["args"]["name"]: e["pid"] for e in events
                if e["ph"] == "M"}
        assert {"domain:serve", f"domain:{HOST_DOMAIN}"} <= set(rows)
        assert rows["domain:serve"] != rows[f"domain:{HOST_DOMAIN}"]

    def test_profiler_trace_holds_program_spans(self, tiny_model, tmp_path):
        """Under a profiler session the annotated spans land in the
        trace's host plane, nested as the program opened them."""
        import jax
        from jax.profiler import ProfileData

        from repro.serving.api import SubmitSpec
        srv = _golden_server(tiny_model)
        srv.submit(SubmitSpec(prompt=np.arange(1, 9), max_new_tokens=2))
        srv.set_host_tracer(Tracer(TraceBuffer(), domain=HOST_DOMAIN,
                                   annotate=True))
        with jax.profiler.trace(str(tmp_path)):
            srv.run_queue()
        srv.set_host_tracer(None)
        (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        events = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("gtrac."):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))

        def inside(inner, outer):
            return any(a <= s and e <= b for s, e in events[inner]
                       for a, b in events[outer])

        ((q0, q1),) = events["gtrac.run_queue"]
        assert len(events["gtrac.window"]) == 2
        for s, e in events["gtrac.window"]:
            assert q0 <= s and e <= q1
        assert len(events["gtrac.hop.dispatch"]) == 4
        assert len(events["gtrac.token_sync"]) == 2
        for name in ("admit", "route", "execute", "token_pick",
                     "token_sync", "emit"):
            assert inside(f"gtrac.{name}", "gtrac.window")
        assert inside("gtrac.hop.dispatch", "gtrac.execute")
        assert inside("gtrac.route.dp", "gtrac.route")
