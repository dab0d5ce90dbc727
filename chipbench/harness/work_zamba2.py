"""Operations and bytes a Zamba2 forward needs, from its shapes alone,
as ``work.py`` counts GPT-2's: recomputed work does not count, so a
faster implementation of the same result never reads as more work.

``model`` is a configuration's Hugging Face Zamba2 ``model`` entry.
Multiply-adds count two; elementwise work (norms, activations, the
conv's SiLU, the scan's exponentials) is not counted.
"""
from __future__ import annotations

from typing import Tuple

#: bytes of a float32, the scan's operands and state
F32 = 4


def _sizes(model: dict) -> dict:
    d, e = int(model["hidden_size"]), int(model["mamba_expand"])
    P = int(model["mamba_headdim"])
    return {"L": int(model["n_layer"]), "d": d, "Di": e * d, "H": e * d // P,
            "P": P, "N": int(model["mamba_d_state"]),
            "G": int(model["mamba_ngroups"]), "W": int(model["mamba_d_conv"]),
            "q": int(model["num_attention_heads"])
            * int(model["attention_head_dim"]),
            "kv": int(model["num_key_value_heads"])
            * int(model["attention_head_dim"]),
            "F": int(model["intermediate_size"]), "V": int(model["vocab_size"]),
            "r": int(model["adapter_rank"]), "C": int(model["chunk_size"]),
            "hybrid": len(model["hybrid_layer_ids"])}


def ssd_step_flops(model: dict) -> float:
    """One position of one layer's scan by its recurrence: per head,
    h = a·h + (dt·x) ⊗ B (three per state entry) and y = C·h (two)."""
    s = _sizes(model)
    return 5.0 * s["H"] * s["N"] * s["P"]


def mixer_position_flops(model: dict) -> float:
    """One position through one Mamba2 mixer: in_proj, the depthwise
    conv, the scan's step and out_proj."""
    s = _sizes(model)
    ch = s["Di"] + 2 * s["G"] * s["N"]
    return (2.0 * s["d"] * (ch + s["Di"] + s["H"]) + 2.0 * s["W"] * ch
            + ssd_step_flops(model) + 2.0 * s["Di"] * s["d"])


def shared_position_flops(model: dict, context: int) -> float:
    """One position through one hybrid layer's shared block, attending to
    ``context`` positions: q, k, v from [x ; x0], q.k and p.v, o_proj,
    gate_up and down, the layer's LoRA and its linear."""
    s = _sizes(model)
    d, q, kv, F, r = s["d"], s["q"], s["kv"], s["F"], s["r"]
    return (2.0 * 2 * d * (q + 2 * kv) + 2.0 * 2 * context * q
            + 2.0 * q * d + 2.0 * d * 2 * F + 2.0 * F * d
            + 2.0 * r * (d + 2 * F) + 2.0 * d * d)


def position_flops(model: dict, context: int) -> float:
    """One position through every layer."""
    s = _sizes(model)
    return (s["L"] * mixer_position_flops(model)
            + s["hybrid"] * shared_position_flops(model, context))


def head_flops(model: dict) -> float:
    """The tied head at one position."""
    s = _sizes(model)
    return 2.0 * s["d"] * s["V"]


def token_flops(model: dict, prompt: int, index: int) -> float:
    """Useful FLOPs of emitting output token ``index`` of a request with a
    ``prompt``-token prompt: the whole prompt once, causally, for the first
    token; one new position for each later one; the head once per token."""
    if index == 0:
        body = sum(position_flops(model, c) for c in range(1, prompt + 1))
    else:
        body = position_flops(model, prompt + index)
    return body + head_flops(model)


def ssd_chunked_work(model: dict, length: int) -> Tuple[float, float]:
    """(ops, bytes) of one layer's chunked scan over ``length`` positions
    from a zero state, in chunks of ``chunk_size`` (the last one as long
    as what is left). Per chunk of c positions: C·Bᵀ per group (2c²N),
    its decayed product with dt·x per head (2c²P), and per head the
    readout of the carried state and the state's update (2cNP each).
    Bytes: dt·x, the log-decays, B and C read, y written, the final
    state written, all float32."""
    s = _sizes(model)
    H, P, N, G, C = s["H"], s["P"], s["N"], s["G"], s["C"]
    ops = 0.0
    for a in range(0, length, C):
        c = min(C, length - a)
        ops += 2.0 * c * c * N * G + H * (2.0 * c * c * P + 4.0 * c * N * P)
    nbytes = F32 * (2 * length * H * P + length * H + 2 * length * G * N
                    + H * N * P)
    return ops, nbytes


def ssd_step_work(model: dict) -> Tuple[float, float]:
    """(ops, bytes) of one layer's recurrent step for one position: the
    state read and written, and x, dt, the decay, B and C read and y
    written, float32."""
    s = _sizes(model)
    H, P, N, G = s["H"], s["P"], s["N"], s["G"]
    return ssd_step_flops(model), F32 * (2 * H * N * P + 2 * H * P + 2 * H
                                         + 2 * G * N)
