"""Compile the served path for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached. This catches what interpret mode cannot: Pallas
lowerings the chip refuses (a scatter inside a kernel), programs that do
not fit the chip's 16 GB, and weights lowered into a program as constants.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.pipeline import StagePartition
from repro.kernels.tropical_route import tropical_route, tropical_route_kbest
from repro.models.api import build_model
from repro.serving.gtrac_serve import served_stage_params, stage_step

V5E_HBM_BYTES = 16 * 1024 ** 3
MAX_PROGRAM_TEXT = 1_000_000      # weights as constants would be GBs

# the served registry of GPT-2 Large: 18 stages x 6 replicas, L = 36
PEERS, LAYERS, K_BEST = 108, 36, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to an enabled cache but
    # cannot be read back without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _route_args(R, one_chip):
    return (_spec((PEERS,), jnp.int32, one_chip),
            _spec((PEERS,), jnp.int32, one_chip),
            _spec((R, PEERS), jnp.float32, one_chip))


@pytest.mark.parametrize("R", [4, 70])
def test_tropical_route_kbest_compiles(one_chip, R):
    compiled = tropical_route_kbest.lower(
        *_route_args(R, one_chip), total_layers=LAYERS,
        k_best=K_BEST).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tropical_route_compiles(one_chip):
    compiled = tropical_route.lower(*_route_args(4, one_chip),
                                    total_layers=LAYERS).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stage_program(cfg, stage, one_chip, first, last, S=250):
    stage = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), stage)
    tokens = _spec((1, S), jnp.int32, one_chip)
    x = None if first else _spec((1, S, cfg.d_model), jnp.bfloat16, one_chip)
    lowered = stage_step.lower(cfg, stage, tokens, x, first=first, last=last)
    assert len(lowered.as_text()) < MAX_PROGRAM_TEXT
    return lowered.compile()


def _unfused_text(compiled) -> str:
    """The program's text without its fused computations: the arrays it
    takes and writes to memory, not the values a fusion keeps on chip."""
    text = compiled.as_text()
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.\-]+)", text))
    blocks = re.split(r"\n(?=%|ENTRY)", text)
    return "\n".join(b for b in blocks if b.split(" ", 1)[0] not in fused)


@pytest.mark.parametrize("layers", [2, 9])
@pytest.mark.parametrize("first,last", [(True, False), (False, False),
                                        (False, True)],
                         ids=["first", "middle", "last"])
def test_gpt2_large_stage_compiles(one_chip, first, last, layers):
    """One full-width GPT-2 Large stage (2 or 9 layers), built as the
    server builds it, fits the chip and takes the weights as arguments
    instead of embedding them. No float32 weight stack or table is
    passed to the program or written by it, as on float32 slices; it
    takes at most 55% of their argument bytes and, at 9 layers, no
    temporary copy of its stack."""
    cfg = get_config("gpt2-large")
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    stages = jax.eval_shape(lambda p: served_stage_params(
        cfg, p, StagePartition.uniform(cfg.num_layers, layers)), shapes)
    i = 0 if first else -1 if last else 1
    compiled = _stage_program(cfg, stages[i], one_chip, first, last)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < V5E_HBM_BYTES

    masters = {**shapes, "layers": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((layers, *a.shape[1:]), a.dtype),
        shapes["layers"])}
    f32 = _stage_program(cfg, masters, one_chip, first, last)
    text, f32_text = _unfused_text(compiled), _unfused_text(f32)
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    stacks = [f"f32[{layers},{d},{f}]", f"f32[{layers},{f},{d}]",
              f"f32[{layers},{d},{d}]"]
    assert all(s in f32_text for s in stacks)
    assert not any(s in text for s in [*stacks, f"f32[{v},{d}]"])
    assert (mem.argument_size_in_bytes
            <= 0.55 * f32.memory_analysis().argument_size_in_bytes)
    if layers == 9:
        assert mem.temp_size_in_bytes < 10 * 1024 ** 2
