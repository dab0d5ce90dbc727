"""Each metric reader on a hand-made run: what it reads, and that it
reads nothing where the run holds nothing for it."""
import pytest

from chipbench.harness import cell as cells
from chipbench.harness import trace as tracing
from chipbench.harness import work
from chipbench.harness.context import RunContext, ServedRequest, WaveRecord
from chipbench.harness.peaks import peaks

MODEL = {"n_layer": 36, "n_embd": 1280, "n_head": 20, "n_inner": None,
         "vocab_size": 50257}
KERNEL_OP = ("%tropical_route_kbest.1 = (f32[1,148]) custom-call(...), "
             'custom_call_target="tpu_custom_call"')


def _ctx(trace=False):
    # two requests due at t=0: 3 and 2 tokens, one emitted every 0.1 s
    reqs = [ServedRequest(40, 3, 3, [0.2, 0.3, 0.4], [100.0, 120.0]),
            ServedRequest(50, 2, 2, [0.25, 0.45], [300.0])]
    wave = WaveRecord(0.0, 1.0, reqs)
    calls = {"admit": [(0.0, 0.001), (0.5, 0.503)],
             "route": [(0.01, 0.02)], "chain": [(0.1, 0.15), (0.2, 0.25)]}
    ctx = RunContext(model=MODEL, waves=[wave], calls=calls,
                     route_calls=[(1, 36, 36, 4, True, 0.01),
                                  (1, 36, 36, 4, False, 0.5)],
                     compiles_in_window=0, setup_s=42.0,
                     peak=peaks("TPU v5 lite"))
    if trace:
        ctx.trace = tracing.Reduced(
            window_s=1.0, busy_s=0.4,
            modules_s={"jit_stage_step(1)": 0.3, "jit_other(2)": 0.05},
            ops_s={KERNEL_OP: 2e-5, "%convert.4 = bf16[2]": 0.1},
            idle_by_label={"wave": 0.6})
        ctx.traced_wave = wave
    return ctx


def _read(name, ctx):
    return cells.metric_reader(name).read(ctx)


def test_end_to_end_readers():
    ctx = _ctx()
    assert _read("tokens_per_s", ctx) == pytest.approx(5.0)
    assert _read("ttft_p90_ms", ctx) == pytest.approx(245.0)
    # gaps 100, 100 and 200 ms
    assert _read("itl_p90_ms", ctx) == pytest.approx(180.0)
    assert _read("modelled_itl_p99_ms", ctx) == pytest.approx(296.4)
    assert _read("setup_s", ctx) == 42.0


def test_host_span_readers():
    ctx = _ctx()
    assert _read("admit_ms_per_window", ctx) == pytest.approx(2.0)
    assert _read("route_ms_per_window", ctx) == pytest.approx(10.0)
    assert _read("chain_host_ms_per_token", ctx) == pytest.approx(20.0)
    assert _read("compiles_in_window", ctx) == 0.0


def test_trace_readers():
    ctx = _ctx(trace=True)
    assert _read("stage_device_ms_per_token", ctx) == pytest.approx(60.0)
    assert _read("device_idle_share", ctx) == pytest.approx(60.0)
    flops = (work.gpt2_token_flops(MODEL, 40, 0)
             + work.gpt2_token_flops(MODEL, 40, 1)
             + work.gpt2_token_flops(MODEL, 40, 2)
             + work.gpt2_token_flops(MODEL, 50, 0)
             + work.gpt2_token_flops(MODEL, 50, 1))
    assert _read("mfu", ctx) == pytest.approx(100 * flops / 197e12)
    need = work.roofline_seconds(*work.kbest_dp_work(1, 36, 36, 4),
                                 peaks("TPU v5 lite"))
    assert _read("tropical_route_kbest_roofline", ctx) == \
        pytest.approx(100 * need / 2e-5)


@pytest.mark.parametrize("name", ["stage_device_ms_per_token", "mfu",
                                  "device_idle_share",
                                  "tropical_route_kbest_roofline"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert _read(name, _ctx()) is None


def test_roofline_reads_nothing_without_the_kernel():
    ctx = _ctx(trace=True)
    ctx.trace.ops_s = {"%convert.4 = bf16[2]": 0.1}
    assert _read("tropical_route_kbest_roofline", ctx) is None
