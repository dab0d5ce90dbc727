"""Compile the served path for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached. This catches what interpret mode cannot: Pallas
lowerings the chip refuses (a scatter inside a kernel), programs that do
not fit the chip's 16 GB, and weights lowered into a program as constants.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.pipeline import slice_stage_params
from repro.kernels.tropical_route import tropical_route, tropical_route_kbest
from repro.models.api import build_model
from repro.serving.gtrac_serve import stage_step

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


@pytest.mark.parametrize("first,last", [(True, False), (False, False),
                                        (False, True)],
                         ids=["first", "middle", "last"])
def test_gpt2_large_stage_compiles(one_chip, first, last):
    """One full-width GPT-2 Large stage (2 layers) fits the chip, and its
    program takes the weights as arguments instead of embedding them."""
    cfg = get_config("gpt2-large")
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    stage = jax.eval_shape(lambda p: slice_stage_params(p, 0, 2), shapes)
    stage = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), stage)
    S = 24
    tokens = _spec((1, S), jnp.int32, one_chip)
    x = None if first else _spec((1, S, cfg.d_model), jnp.bfloat16, one_chip)
    lowered = stage_step.lower(cfg, stage, tokens, x, first=first, last=last)
    assert len(lowered.as_text()) < MAX_PROGRAM_TEXT
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < V5E_HBM_BYTES
