"""Operations and bytes of the useful work, from shapes alone.

Every count reads the work the model needs, whatever later computes it:
actual prompt lengths (not the padded buckets), causal attention as
the half it is (``n (n + 1) / 2`` query-key pairs), no recomputation,
only the logits that are used.  Padding, masked blocks or recomputed
work then lower a share; they can never raise it past 100%.

``m`` is the ``model`` dict of a configuration file.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def layer_matmul_params(m: Dict) -> int:
    D, H, KH, Dh, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return D * H * Dh + 2 * D * KH * Dh + H * Dh * D + 3 * D * F


def _pairs(n: int) -> int:
    return n * (n + 1) // 2


def attn_pair_flops(m: Dict) -> int:
    """FLOPs of one query-key pair over all heads: q.k and p.v."""
    return 4 * m["num_heads"] * m["head_dim"]


def head_flops(m: Dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: Dict, plen: int) -> int:
    """One prompt: every layer over ``plen`` tokens, causal attention,
    the head for the last position only."""
    L = m["num_layers"]
    return (L * (2 * layer_matmul_params(m) * plen
                 + attn_pair_flops(m) * _pairs(plen)) + head_flops(m))


def decode_flops(m: Dict, kv_len: int) -> int:
    """One decoded token attending to ``kv_len`` cached positions."""
    L = m["num_layers"]
    return (L * (2 * layer_matmul_params(m) + attn_pair_flops(m) * kv_len)
            + head_flops(m))


def train_flops(m: Dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step; the head
    counts the ``seq - 1`` positions that have a target."""
    L = m["num_layers"]
    fwd = (L * (2 * layer_matmul_params(m) * seq
                + attn_pair_flops(m) * _pairs(seq))
           + head_flops(m) * (seq - 1))
    return 3 * batch * fwd


def flash_fwd(m: Dict, lens: Iterable[int], layers: int = 0) -> Tuple[int, int]:
    """(FLOPs, bytes) of the causal attention forward over sequences of
    ``lens`` tokens, all layers: read q, k, v and write the output once,
    in bfloat16."""
    L = layers or m["num_layers"]
    H, KH, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = bytes_ = 0
    for n in lens:
        flops += attn_pair_flops(m) * _pairs(n)
        bytes_ += n * (2 * H + 2 * KH) * Dh * BF16
    return L * flops, L * bytes_


def paged_decode(m: Dict, kv_lens: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of single-token attention through the page table,
    all layers: read each live slot's ``kv_len`` keys and values, its
    query and write its output, in bfloat16."""
    L = m["num_layers"]
    H, KH, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = bytes_ = 0
    for kv in kv_lens:
        flops += attn_pair_flops(m) * kv
        bytes_ += (2 * KH * kv * Dh + 2 * H * Dh) * BF16
    return L * flops, L * bytes_


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time the chip could take (the larger of the compute and
    the memory bound) over the time taken, in %."""
    bound = max(flops / peak["bf16_flops_per_s"],
                bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
