"""repro.analysis — the AST invariant linter (PR 10).

Each rule gets golden fixture tests seeded with its historical bug
class (PR 5 aliasing, PR 6 clock back-dating, PR 8 global RNG, PR 9
unguarded spans) plus the corrected form; the framework gets
suppression / allow-list / JSON-schema / exit-code coverage; and a
meta-test asserts the live tree is clean under the shipped allow-list.
"""
import ast
import json
import os
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (
    AllowEntry,
    Config,
    ConfigError,
    analyze_file,
    analyze_paths,
    build_rules,
    load_config,
    registry_mutator_info,
    registry_mutators,
)
from repro.analysis.core import (
    UNUSED_ALLOW,
    UNUSED_SUPPRESSION,
    FileContext,
    Walker,
)
from repro.analysis.rules import classify_method

REPO_ROOT = Path(__file__).resolve().parent.parent


def findings_in(src, path="src/repro/serving/snippet.py", options=None):
    """Run all rules over a source snippet pretending it lives at
    ``path`` (rule path scoping keys on it). Suppressions/allow-lists
    are NOT applied — this is the raw rule layer."""
    src = textwrap.dedent(src)
    ctx = FileContext(path, ast.parse(src), src.splitlines())
    Walker(build_rules(options)).run(ctx)
    return ctx.findings


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# clock-discipline (PR 6 maybe_tick back-dating / PR 9 clock domains)
# ---------------------------------------------------------------------------


class TestClockDiscipline:
    def test_flags_wall_clock_in_sync_domain(self):
        # the PR 6 bug class: a lease validator reading the wall clock
        # directly, so sim-time leases compare against real time
        src = """
            import time

            def maybe_tick(self, lease):
                now = time.time()
                return lease.expiry > now
        """
        fs = findings_in(src, path="src/repro/sync/lease.py")
        assert rule_ids(fs) == ["clock-discipline"]
        assert "time.time()" in fs[0].message

    def test_flags_aliased_import_and_from_import(self):
        src = """
            import time as _time
            from time import perf_counter

            def f():
                return _time.monotonic() + perf_counter()
        """
        fs = findings_in(src, path="src/repro/serving/x.py")
        assert len(fs) == 2
        assert rule_ids(fs) == ["clock-discipline"]

    def test_injected_clock_is_clean(self):
        src = """
            def maybe_tick(self, lease):
                now = self.clock()
                return lease.expiry > now
        """
        assert findings_in(src, path="src/repro/sync/lease.py") == []

    def test_outside_sim_domains_is_exempt(self):
        src = """
            import time

            def stamp():
                return time.time()
        """
        assert findings_in(src, path="src/repro/trainer/loop.py") == []


# ---------------------------------------------------------------------------
# rng-discipline (PR 8 one-draw-per-hop determinism)
# ---------------------------------------------------------------------------


class TestRngDiscipline:
    def test_flags_global_numpy_rng(self):
        # the PR 8 bug class: global RNG state breaks bit-identical
        # parity across layers the moment call order shifts
        src = """
            import numpy as np

            def jitter(n):
                np.random.seed(0)
                return np.random.rand(n)
        """
        fs = findings_in(src, path="src/repro/core/x.py")
        assert len(fs) == 2 and rule_ids(fs) == ["rng-discipline"]

    def test_flags_unseeded_default_rng(self):
        src = """
            import numpy as np

            def pick(xs):
                rng = np.random.default_rng()
                return xs[rng.integers(len(xs))]
        """
        fs = findings_in(src, path="src/repro/core/x.py")
        assert len(fs) == 1 and "unseeded" in fs[0].message

    def test_flags_stdlib_random(self):
        src = """
            import random
            from random import shuffle

            def scramble(xs):
                shuffle(xs)
                return random.choice(xs)
        """
        fs = findings_in(src, path="src/repro/core/x.py")
        assert len(fs) == 2 and rule_ids(fs) == ["rng-discipline"]

    def test_seeded_and_passed_generators_are_clean(self):
        src = """
            import numpy as np
            from numpy.random import default_rng

            def pick(xs, rng, seed, i):
                r2 = np.random.default_rng([seed, i])
                r3 = default_rng(seed)
                g = np.random.Generator(np.random.PCG64(seed))
                return xs[rng.integers(len(xs))]
        """
        assert findings_in(src, path="src/repro/core/x.py") == []


# ---------------------------------------------------------------------------
# state-aliasing (PR 5 history === mirror)
# ---------------------------------------------------------------------------


class TestStateAliasing:
    def test_flags_stored_export_pr5_bug_class(self):
        # the PR 5 bug verbatim: seeker stores the publisher's state
        # object, so a later heartbeat refresh corrupts shipped deltas
        src = """
            def apply(self, shard, full):
                self._states[shard] = full.export_state()
        """
        fs = findings_in(src, path="src/repro/sync/seeker.py")
        assert rule_ids(fs) == ["state-aliasing"]

    def test_flags_taint_through_locals_and_history_dicts(self):
        src = """
            def shard_state(self, shard, version):
                state = registry_shard_state(self.reg, shard)
                hist = self._history.setdefault(shard, {})
                hist[version] = state
                return state
        """
        fs = findings_in(src, path="src/repro/sync/pub.py")
        assert len(fs) == 1 and fs[0].rule == "state-aliasing"

    def test_flags_adopt_of_shared_state(self):
        src = """
            def tick(self, primary, backups):
                states = {}
                for s in range(4):
                    states[s] = primary.export_shard_state(s)
                for rep in backups:
                    rep.adopt_shard_state(0, states[0])
                state = primary.export_state()
                for rep in backups:
                    rep.adopt_state(state)
        """
        fs = findings_in(src, path="src/repro/core/x.py")
        assert len(fs) == 2 and rule_ids(fs) == ["state-aliasing"]

    def test_flags_stored_delta_full(self):
        src = """
            def apply(self, shard, delta):
                self._states[shard] = delta.full
        """
        fs = findings_in(src, path="src/repro/sync/seeker.py")
        assert rule_ids(fs) == ["state-aliasing"]

    def test_copy_state_sanitizes(self):
        # the PR 5 fix shape: copy on adopt
        src = """
            def apply(self, shard, delta):
                new = copy_state(delta.full)
                self._states[shard] = new
                self._snap[shard] = copy_state(self.reg.export_state())
        """
        assert findings_in(src, path="src/repro/sync/seeker.py") == []

    def test_readonly_use_is_clean(self):
        src = """
            def digest_of(self, shard):
                st = self.mirror.mirror(shard)
                return state_digest(st, self.seed)
        """
        assert findings_in(src, path="src/repro/sync/x.py") == []


# ---------------------------------------------------------------------------
# version-bump (snapshot-versioning contract)
# ---------------------------------------------------------------------------

_REG_TMPL = """
    class AnchorRegistry:
        def set_trust(self, peer_id, trust):
            rec = self.peers.get(peer_id)
            rec.trust = trust
            %s

        def heartbeat(self, peer_id, now):
            rec = self.peers.get(peer_id)
            rec.last_heartbeat = now

        def __init__(self, cfg):
            self._peers = {}
"""


class TestVersionBump:
    def test_flags_undischarged_mutator(self):
        src = _REG_TMPL % "return rec"
        fs = findings_in(src, path="src/repro/core/registry.py")
        assert rule_ids(fs) == ["version-bump"]
        assert "set_trust" in fs[0].message and "trust" in fs[0].message

    @pytest.mark.parametrize("discharge", [
        "self._touch()", "self.version += 1", "self._mirror = None"])
    def test_touch_bump_or_invalidation_discharges(self, discharge):
        src = _REG_TMPL % discharge
        assert findings_in(src, path="src/repro/core/registry.py") == []

    def test_heartbeat_only_and_init_are_exempt(self):
        # the template's heartbeat/__init__ never discharge, yet the
        # clean variants above produce zero findings for them
        src = _REG_TMPL % "self._touch()"
        assert findings_in(src, path="src/repro/core/registry.py") == []

    def test_registry_classes_option(self):
        src = """
            class OtherRegistry:
                def zap(self):
                    self._peers.clear()
        """
        assert findings_in(src, path="src/repro/core/x.py") == []
        fs = findings_in(
            src, path="src/repro/core/x.py",
            options={"version-bump": {"registry_classes": ["OtherRegistry"]}})
        assert rule_ids(fs) == ["version-bump"]

    def test_classifier_on_live_registry(self):
        info = registry_mutator_info()
        assert info["heartbeat"].heartbeat_only
        assert info["adopt_heartbeats"].heartbeat_only
        assert info["sweep"].mutates and info["sweep"].discharged
        assert not info["snapshot"].mutates
        assert not info["export_state"].mutates

    def test_derived_mutator_set_is_the_public_nine(self):
        assert registry_mutators() == frozenset({
            "register", "deregister", "heartbeat", "sweep", "apply_report",
            "set_trust", "reset_trust", "adopt_state", "adopt_heartbeats"})

    def test_classify_method_fields(self):
        fn = ast.parse(textwrap.dedent("""
            def bump_all(self):
                for rec in self.peers.values():
                    rec.successes += 1
        """)).body[0]
        info = classify_method(fn)
        assert info.mutates and info.fields == {"successes"}
        assert info.violating


# ---------------------------------------------------------------------------
# tracer-guard (PR 9 hot-path guards)
# ---------------------------------------------------------------------------


class TestTracerGuard:
    def test_flags_unguarded_span_pr9_bug_class(self):
        # the PR 9 bug class: an event emitted per request with tracing
        # disabled still pays dict/list work on the hot path
        src = """
            def route(self, req):
                self.tracer.event("route", rid=req.id)
                return self._route(req)
        """
        fs = findings_in(src, path="src/repro/serving/server.py")
        assert rule_ids(fs) == ["tracer-guard"]

    def test_enabled_guard_is_clean(self):
        src = """
            def route(self, req):
                if self.tracer.enabled:
                    self.tracer.event("route", rid=req.id)
                return self._route(req)
        """
        assert findings_in(src, path="src/repro/serving/server.py") == []

    def test_traced_alias_guard_is_clean(self):
        src = """
            def run(self, reqs):
                tr = self.tracer
                traced = tr.enabled
                for r in reqs:
                    if traced:
                        tr.event("tick", rid=r.id)
        """
        assert findings_in(src, path="src/repro/serving/server.py") == []

    def test_span_is_none_pattern_is_clean(self):
        src = """
            def window(self):
                tr = self.tracer
                sp = tr.begin("window") if tr.enabled else None
                self.step()
                if sp is not None:
                    tr.end(sp, t1=self.now)
        """
        assert findings_in(src, path="src/repro/serving/server.py") == []

    def test_flags_unguarded_host_tracer_span(self):
        # the host tracer spans every window phase and hop dispatch: an
        # unguarded site builds kwargs and a span per token with tracing
        # off, directly or through an alias
        direct = """
            def hop(self, payload):
                with self.host_tracer.span("hop.dispatch", stage=1):
                    return self.fn(payload)
        """
        alias = """
            def run(self, reqs):
                ht = self.host_tracer
                for r in reqs:
                    with ht.span("execute"):
                        self.execute(r)
        """
        for src in (direct, alias):
            fs = findings_in(src, path="src/repro/serving/server.py")
            assert rule_ids(fs) == ["tracer-guard"]

    def test_guarded_host_tracer_span_is_clean(self):
        src = """
            def run(self, reqs):
                ht = self.host_tracer
                hon = ht.enabled
                for r in reqs:
                    with (ht.span("execute") if hon else NOOP_SPAN):
                        self.execute(r)
                with (self.host_tracer.span("finish")
                      if self.host_tracer.enabled else NOOP_SPAN):
                    self.finish()
        """
        assert findings_in(src, path="src/repro/serving/server.py") == []

    def test_else_branch_of_guard_still_flags(self):
        src = """
            def route(self, req):
                if self.tracer.enabled:
                    pass
                else:
                    self.tracer.event("route", rid=req.id)
        """
        fs = findings_in(src, path="src/repro/serving/server.py")
        assert rule_ids(fs) == []  # orelse of a guard is a deliberate path

    def test_obs_package_is_exempt(self):
        src = """
            def begin(self, name):
                self.tracer.event(name)
        """
        assert findings_in(src, path="src/repro/obs/trace.py") == []

    def test_set_add_is_not_a_tracer(self):
        src = """
            def dedupe(self, xs):
                seen = set()
                for x in xs:
                    seen.add(x)
        """
        assert findings_in(src, path="src/repro/serving/server.py") == []


# ---------------------------------------------------------------------------
# wire-safety (PR 7 pickled control-plane transport)
# ---------------------------------------------------------------------------


class TestWireSafety:
    def test_flags_lambda_in_payload(self):
        src = """
            def kick(self, q, rid):
                q.put((rid, "apply", lambda reg: reg.sweep(0.0)))
        """
        fs = findings_in(src, path="src/repro/control_plane/x.py")
        assert rule_ids(fs) == ["wire-safety"]
        assert "lambda" in fs[0].message

    def test_flags_payload_via_local_name(self):
        src = """
            def kick(self, tr, rid, rows):
                msg = (rid, "rows", (r for r in rows))
                tr.post(msg)
        """
        fs = findings_in(src, path="src/repro/control_plane/x.py")
        assert rule_ids(fs) == ["wire-safety"]

    def test_flags_locally_defined_object(self):
        src = """
            def kick(self, q, rid):
                def helper(reg):
                    return reg.version
                q.put((rid, "call", helper))
        """
        fs = findings_in(src, path="src/repro/control_plane/x.py")
        assert rule_ids(fs) == ["wire-safety"]

    def test_plain_tuple_payload_is_clean(self):
        src = """
            def kick(self, q, rid, op, args):
                q.put((rid, op, args))
        """
        assert findings_in(src, path="src/repro/control_plane/x.py") == []

    def test_outside_control_plane_is_exempt(self):
        src = """
            def enqueue(self, q):
                q.put(lambda: 1)
        """
        assert findings_in(src, path="src/repro/serving/x.py") == []


# ---------------------------------------------------------------------------
# framework: suppressions, allow-list, JSON, exit codes, meta
# ---------------------------------------------------------------------------

_RNG_SNIPPET = textwrap.dedent("""
    import numpy as np

    def pick(xs):
        rng = np.random.default_rng(){}
        return xs[rng.integers(len(xs))]
""")


class TestSuppressions:
    def _lint_file(self, tmp_path, body):
        p = tmp_path / "snippet.py"
        p.write_text(body)
        return analyze_file(str(p), build_rules())

    def test_inline_suppression_silences_finding(self, tmp_path):
        rep = self._lint_file(
            tmp_path,
            _RNG_SNIPPET.format("  # repolint: allow[rng-discipline]"))
        assert rep.findings == [] and rep.suppressed == 1

    def test_comment_line_above_covers_next_line(self, tmp_path):
        body = _RNG_SNIPPET.format("").replace(
            "    rng =",
            "    # repolint: allow[rng-discipline]\n    rng =")
        rep = self._lint_file(tmp_path, body)
        assert rep.findings == [] and rep.suppressed == 1

    def test_without_suppression_finding_stands(self, tmp_path):
        rep = self._lint_file(tmp_path, _RNG_SNIPPET.format(""))
        assert rule_ids(rep.findings) == ["rng-discipline"]

    def test_unused_suppression_is_a_finding(self, tmp_path):
        body = "x = 1  # repolint: allow[rng-discipline]\n"
        rep = self._lint_file(tmp_path, body)
        assert rule_ids(rep.findings) == [UNUSED_SUPPRESSION]

    def test_unknown_rule_in_suppression_is_a_finding(self, tmp_path):
        body = "x = 1  # repolint: allow[no-such-rule]\n"
        rep = self._lint_file(tmp_path, body)
        assert rule_ids(rep.findings) == [UNUSED_SUPPRESSION]
        assert "unknown rule" in rep.findings[0].message


class TestAllowList:
    def test_allow_entry_moves_finding_and_prints_why(self, tmp_path):
        p = tmp_path / "snip.py"
        p.write_text(_RNG_SNIPPET.format(""))
        rel = os.path.relpath(str(p)).replace(os.sep, "/")
        cfg = Config(allow=[AllowEntry(
            rule="rng-discipline", path=rel,
            why="fixture: deliberate")])
        run = analyze_paths([str(p)], build_rules(), cfg)
        assert run.findings == []
        assert len(run.allowed) == 1 and run.allowed[0][1].startswith(
            "fixture")

    def test_unused_allow_entry_is_a_finding(self, tmp_path):
        p = tmp_path / "clean.py"
        p.write_text("x = 1\n")
        rel = os.path.relpath(str(p)).replace(os.sep, "/")
        cfg = Config(allow=[AllowEntry(
            rule="rng-discipline", path=rel, why="stale")])
        run = analyze_paths([str(p)], build_rules(), cfg)
        assert rule_ids(run.findings) == [UNUSED_ALLOW]

    def test_config_validation(self, tmp_path):
        bad = tmp_path / "repolint.json"
        bad.write_text(json.dumps(
            {"allow": [{"rule": "rng-discipline", "path": "x.py"}]}))
        with pytest.raises(ConfigError, match="missing"):
            load_config(str(bad), ["rng-discipline"])
        bad.write_text(json.dumps(
            {"allow": [{"rule": "bogus", "path": "x.py", "why": "w"}]}))
        with pytest.raises(ConfigError, match="unknown rule"):
            load_config(str(bad), ["rng-discipline"])
        bad.write_text("{not json")
        with pytest.raises(ConfigError, match="valid JSON"):
            load_config(str(bad), ["rng-discipline"])

    def test_shipped_config_loads(self):
        cfg = load_config(str(REPO_ROOT / "repolint.json"),
                          [r.rule_id for r in build_rules()])
        assert cfg.allow and all(e.why.strip() for e in cfg.allow)


class TestCliAndJson:
    def test_json_schema(self, tmp_path, monkeypatch, capsys):
        from repro.analysis.__main__ import main
        p = tmp_path / "snip.py"
        p.write_text(_RNG_SNIPPET.format(""))
        monkeypatch.chdir(tmp_path)
        rc = main(["--json", "--no-config", "snip.py"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert set(out) == {"version", "config", "files", "findings",
                            "allowed", "summary"}
        (f,) = out["findings"]
        assert set(f) == {"rule", "path", "line", "col", "message",
                          "symbol"}
        assert f["rule"] == "rng-discipline" and f["symbol"] == "pick"
        assert out["summary"] == {"findings": 1, "allowed": 0}

    def test_exit_codes(self, tmp_path, monkeypatch, capsys):
        from repro.analysis.__main__ import main
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        monkeypatch.chdir(tmp_path)
        assert main(["--no-config", "clean.py"]) == 0
        assert main(["--no-config", "missing.py"]) == 2
        (tmp_path / "repolint.json").write_text("{not json")
        assert main(["clean.py"]) == 2
        capsys.readouterr()

    def test_list_rules(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("clock-discipline", "rng-discipline", "state-aliasing",
                    "version-bump", "tracer-guard", "wire-safety"):
            assert rid in out


class TestLiveTree:
    def test_live_tree_is_clean_under_shipped_allowlist(self, monkeypatch,
                                                        capsys):
        """The acceptance gate: `python -m repro.analysis src/repro`
        exits 0 on the shipped tree, with every exception justified."""
        from repro.analysis.__main__ import main
        monkeypatch.chdir(REPO_ROOT)
        rc = main(["src/repro"])
        out = capsys.readouterr().out
        assert rc == 0, f"live tree has unallowed findings:\n{out}"
        assert "0 finding(s)" in out
