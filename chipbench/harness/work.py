"""Operations and bytes the algorithms need, from their shapes alone.

These count what the computation requires, not what an implementation
happens to do: recomputed work does not count, so a faster
implementation of the same result never reads as more work.
"""
from __future__ import annotations


def gpt2_position_flops(model: dict, context: int) -> float:
    """FLOPs of one position through every block of a GPT-2, attending to
    ``context`` positions: the q, k, v and output projections, the MLP,
    and q.k and p.v over the context. Multiply-adds count two."""
    d = int(model["n_embd"])
    f = int(model.get("n_inner") or 4 * d)
    per_layer = 2 * (4 * d * d + 2 * d * f) + 2 * 2 * context * d
    return float(int(model["n_layer"]) * per_layer)


def gpt2_head_flops(model: dict) -> float:
    """FLOPs of the tied head at one position."""
    return 2.0 * int(model["n_embd"]) * int(model["vocab_size"])


def gpt2_token_flops(model: dict, prompt: int, index: int) -> float:
    """Useful FLOPs of emitting output token ``index`` of a request with
    a ``prompt``-token prompt: the whole prompt once, causally, for the
    first token; one new position for each later one; the head once per
    token."""
    if index == 0:
        body = sum(gpt2_position_flops(model, c) for c in
                   range(1, prompt + 1))
    else:
        body = gpt2_position_flops(model, prompt + index)
    return body + gpt2_head_flops(model)


def kbest_dp_work(R: int, P: int, L: int, K: int):
    """(ops, bytes) of a K-best layered shortest-path DP over ``P`` peers
    spanning ``L`` layers, for ``R`` trust floors.

    ops: each peer extends each of the K best paths into its start
    boundary by one addition and one comparison, per floor: 2·R·P·K.
    bytes: the peers' start and end boundaries (int32), the (R, P) f32
    pruned costs in, and the (R, L+1, K) distances, predecessor peers and
    predecessor ranks out (4 bytes each)."""
    ops = 2.0 * R * P * K
    nbytes = 4.0 * (2 * P + R * P + 3 * R * (L + 1) * K)
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
