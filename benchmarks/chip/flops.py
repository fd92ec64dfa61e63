"""Operations and bytes that the algorithm needs, from shapes.

Counts are of useful work: live batch rows only, attention over each
sequence's live context only. ``arch`` uses the published config keys.
"""
from __future__ import annotations

from typing import Sequence


def layer_matmul_params(arch: dict) -> int:
    d, f = arch["hidden_size"], arch["intermediate_size"]
    h, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    return d * (h + 2 * hkv) * hd + h * hd * d + 3 * d * f


def decode_step_flops(arch: dict, ctx: Sequence[int]) -> float:
    """One decode step of the whole model for live rows whose caches hold
    ``ctx`` tokens before the step: projections, FFN and head at 2 FLOPs
    a multiply-add, attention (QK^T and PV) over ctx + 1 keys."""
    n = arch["num_hidden_layers"]
    rows = len(ctx)
    mm = 2 * rows * (n * layer_matmul_params(arch)
                     + arch["hidden_size"] * arch["vocab_size"])
    attn = n * paged_decode_flops(arch, ctx)
    return float(mm + attn)


def paged_decode_flops(arch: dict, ctx: Sequence[int]) -> float:
    """One paged-decode attention call (one layer)."""
    h, hd = arch["num_attention_heads"], arch["head_dim"]
    return float(sum(4 * (c + 1) * h * hd for c in ctx))


def paged_decode_bytes(arch: dict, ctx: Sequence[int], page: int,
                       dtype_bytes: int = 2) -> float:
    """One paged-decode attention call (one layer): the live pages of K and
    V of every live row, plus q and the output."""
    h, hkv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    pages = sum(-(-(c + 1) // page) for c in ctx)
    kv = 2 * pages * page * hkv * hd * dtype_bytes
    return float(kv + 2 * len(ctx) * h * hd * dtype_bytes)


def roofline_seconds(flops: float, nbytes: float, pk: dict):
    """(least seconds, bound) on a chip with peaks ``pk``."""
    t_c = flops / pk["bf16_flops"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
