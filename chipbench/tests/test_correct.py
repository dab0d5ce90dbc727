"""``correct`` at a test size on the CPU: sound runs pass; the control
(the reference at float8 in the program's place) and each fault the
cells can have fail. The harness's look for a chip is skipped; the rest
of a run is driven as on the chip."""
import dataclasses
import time

import pytest

from chipbench import control
from chipbench import run as bench
from chipbench.harness.cell import BENCH, Cell, load_json

MIX = load_json(BENCH / "tests" / "data" / "tiny-chat.json")
SEEDS = (1, 2, 3)


def _cell(name="tiny.golden-18x2"):
    cfg = load_json(BENCH / "tests" / "data" / f"{name}.json")
    return Cell(name, 1, cfg, MIX, [], [])


def _run(cell, seed):
    out = bench.serve(cell, seed, 0, False, require_tpu=False,
                      t_start=time.perf_counter(), log=lambda m: None)
    return bench.check(cell, out, seed, log=lambda m: None)


@pytest.mark.parametrize("name", ["tiny.golden-18x2", "tiny.paper-4x9"])
def test_sound_runs_are_correct(name):
    checks, ok = _run(_cell(name), 2 ** 31 + 3)
    assert ok, checks
    assert checks["route_mismatches"]["value"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails(seed):
    """The reference at float8, decoding in the program's place, is not
    correct by the same check that the program's float32-weight,
    bfloat16-activation path passes on the same requests."""
    cell = _cell()
    out = bench.serve(cell, seed, 0, False, require_tpu=False,
                      t_start=time.perf_counter(), log=lambda m: None)
    checks, ok = bench.check(cell, out, seed, log=lambda m: None)
    ctrl = control.control_outcome(cell, out, seed)
    c_checks, c_ok = bench.check(cell, ctrl, seed, log=lambda m: None)
    assert ok, checks
    assert not c_ok
    assert c_checks["logit_gap"]["value"] > c_checks["logit_gap"]["limit"]
    assert c_checks["route_mismatches"]["value"] == 0
    n = min(len(s.output) for s in ctrl.served)
    assert len(ctrl.served) == cell.config["correct"]["sample_requests"]
    assert n == min(s.max_new_tokens for s in ctrl.served) > 0


def test_altered_token_fails(monkeypatch):
    from repro.serving.gtrac_serve import GTRACPipelineServer

    emit = GTRACPipelineServer._emit_token
    monkeypatch.setattr(GTRACPipelineServer, "_emit_token",
                        lambda self, req, tok, t: emit(self, req,
                                                       (tok + 1) % 512, t))
    checks, ok = _run(_cell(), 5)
    assert not ok and checks["logit_gap"]["value"] > \
        checks["logit_gap"]["limit"]


def test_stage_returning_its_state_unchanged_fails(monkeypatch):
    from repro.serving import gtrac_serve

    make = gtrac_serve.make_stage_fns

    def skip_stage_1(*args):
        fns = make(*args)
        fns[1] = lambda payload: payload
        return fns

    monkeypatch.setattr(gtrac_serve, "make_stage_fns", skip_stage_1)
    checks, ok = _run(_cell(), 6)
    assert not ok and checks["logit_gap"]["value"] > \
        checks["logit_gap"]["limit"]


def test_altered_route_fails(monkeypatch):
    """The router serves its most expensive alternate as the primary."""
    from repro.serving.batch_router import BatchRouter

    route = BatchRouter.route_window

    def reversed_plans(self, table):
        plans = route(self, table)
        return {rid: dataclasses.replace(p, chain_rows=p.chain_rows[::-1],
                                         costs=p.costs[::-1])
                for rid, p in plans.items()}

    monkeypatch.setattr(BatchRouter, "route_window", reversed_plans)
    checks, ok = _run(_cell(), 7)
    assert not ok and checks["route_mismatches"]["value"] > 0
