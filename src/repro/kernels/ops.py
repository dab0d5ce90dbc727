"""Public jit'd kernel wrappers with backend dispatch.

``impl`` resolution: "pallas" requires a TPU backend (or interpret=True for
CPU validation); "xla" falls back to the pure-jnp oracle-equivalent path.
``auto`` picks pallas on TPU, xla elsewhere — so the same model code runs on
this CPU container (dry-run / tests) and on a real pod.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rwkv6_chunk import wkv6_chunked as _wkv6_pallas
from repro.kernels.ssd_chunk import ssd_chunked as _ssd_pallas
from repro.kernels.tropical_route import (
    tropical_route as _tropical_pallas,
    tropical_route_kbest as _tropical_kbest_pallas,
)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "xla"
    return impl


def flash_attention(q, k, v, *, causal: bool = True, impl: str = "auto",
                    interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _flash_pallas(q, k, v, causal=causal, interpret=interpret,
                             **kw)
    return ref.attention_ref(q, k, v, causal=causal)


def decode_attention(q, cache_k, cache_v, kv_len, *, impl: str = "auto",
                     interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _decode_pallas(q, cache_k, cache_v, kv_len,
                              interpret=interpret, **kw)
    return ref.decode_attention_ref(q, cache_k, cache_v, kv_len)


def tropical_route(starts, ends, costs, *, total_layers: int,
                   impl: str = "auto", interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _tropical_pallas(starts, ends, costs,
                                total_layers=total_layers,
                                interpret=interpret, **kw)
    # XLA fallback: the same DP in jnp (routing_jax.layered_dp)
    from repro.core.routing_jax import layered_dp
    return layered_dp(starts, ends, costs, total_layers=total_layers)


def tropical_route_kbest(starts, ends, costs, *, total_layers: int,
                         k_best: int, impl: str = "auto",
                         interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _tropical_kbest_pallas(starts, ends, costs,
                                      total_layers=total_layers,
                                      k_best=k_best, interpret=interpret,
                                      **kw)
    # XLA fallback: the same K-best DP in jnp (routing_jax)
    from repro.core.routing_jax import layered_dp_kbest
    return layered_dp_kbest(starts, ends, costs, total_layers=total_layers,
                            k_best=k_best)


def wkv6(r, k, v, lw, u, state0, *, impl: str = "auto",
         interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _wkv6_pallas(r, k, v, lw, u, state0, interpret=interpret,
                            **kw)
    return ref.wkv6_ref(r, k, v, lw, u, state0)


def ssd(dx, la, Bm, Cm, h0, *, impl: str = "auto",
        interpret: bool = False, **kw):
    impl = _resolve("pallas" if interpret else impl)
    if impl == "pallas":
        return _ssd_pallas(dx, la, Bm, Cm, h0, interpret=interpret, **kw)
    return ref.ssd_ref(dx, la, Bm, Cm, h0)
