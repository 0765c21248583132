"""Operations and bytes the served model needs, and the chips' peaks.

Work is counted from the published architecture and the live lengths, so
it is the same whatever implements it: empty slots, padding rows and pages,
and copies of pages gathered for attention are never work. Weights are
bfloat16 (2 bytes); the K/V cache holds bfloat16 keys and values.

* FLOPs: 2 per multiply-add of every linear layer for every token computed,
  4 * heads * head_dim per (query, visible key) pair per layer for the two
  attention products, and 2 * d * vocab for every row whose logits are
  computed (every decode row; the last row of a prefill dispatch).
* Bytes: every weight once per dispatched step (the embedding only for the
  rows looked up), the K/V of the live context read once, and the new
  tokens' K/V written once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from bench.reference import Arch

W_BYTES = 2
KV_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    source: str


# Copied from the program's table (repro.obs.profile.DEVICE_PEAKS) so that
# a change to the program cannot move the yardstick.
DEVICE_PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": per chip 197 '
               'TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]


def layer_params(a: Arch) -> int:
    """Weights of one decoder layer (norms and biases included)."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    n = a.d * q + 2 * a.d * kv + q * a.d + 3 * a.d * a.ff + 2 * a.d
    if a.qkv_bias:
        n += q + 2 * kv
    if a.qk_norm:
        n += 2 * a.head_dim
    return n


def linear_flops_per_token(a: Arch) -> int:
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return 2 * a.layers * (a.d * q + 2 * a.d * kv + q * a.d + 3 * a.d * a.ff)


def head_flops_per_row(a: Arch) -> int:
    return 2 * a.d * a.vocab


def attn_flops(a: Arch, pairs: int) -> int:
    """``pairs`` (query, visible key) pairs through every layer."""
    return 4 * a.layers * a.heads * a.head_dim * pairs


def weight_bytes(a: Arch) -> int:
    """Weights one step reads: every layer, the final norm and the head."""
    return W_BYTES * (a.layers * layer_params(a) + a.d + a.d * a.vocab)


def kv_bytes_per_token(a: Arch) -> int:
    return KV_BYTES * 2 * a.layers * a.kv_heads * a.head_dim


def decode_work(a: Arch, k: int, rows: int, ctx: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of ``k`` fused decode ticks of ``rows`` sequences
    whose live lengths sum to ``ctx`` before the first tick. Tick j's row i
    attends its len_i + j + 1 keys."""
    pairs = k * ctx + rows * k * (k + 1) // 2
    toks = k * rows
    flops = toks * (linear_flops_per_token(a) + head_flops_per_row(a)) \
        + attn_flops(a, pairs)
    byt = k * weight_bytes(a) + toks * W_BYTES * a.d \
        + (pairs - toks) * kv_bytes_per_token(a) \
        + toks * kv_bytes_per_token(a)
    return flops, byt


def prefill_work(a: Arch, s: int, start: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill dispatch: ``s`` prompt tokens landed
    after ``start`` tokens already in the cache, logits for the last row."""
    pairs = s * start + s * (s + 1) // 2
    flops = s * linear_flops_per_token(a) + attn_flops(a, pairs) \
        + head_flops_per_row(a)
    byt = weight_bytes(a) + s * W_BYTES * a.d \
        + start * kv_bytes_per_token(a) + s * kv_bytes_per_token(a)
    return flops, byt


def least_time(flops: int, byt: int, p: Peaks) -> float:
    """Seconds the chip needs at best: the larger of the two bounds."""
    return max(flops / p.bf16_flops, byt / p.hbm_bw)
