"""Operations and bytes of the causal flash-attention kernels, from the
call's shapes. Causal attention counts the lower triangle with the diagonal:
S (S + 1) / 2 query-key pairs per head. Bytes are what the algorithm must
move once: each operand read once, each result written once."""

from __future__ import annotations

from typing import Dict


def _pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def forward(batch: int, heads: int, seq: int, head_dim: int,
            bytes_per_el: int = 2) -> Dict[str, float]:
    """o = softmax(q k^T) v: two products per pair."""
    flops = 2.0 * 2.0 * batch * heads * _pairs(seq) * head_dim
    tensor = batch * heads * seq * head_dim * bytes_per_el
    stats = batch * heads * seq * 4                 # log-sum-exp, float32
    return {"flops": flops, "bytes": 4.0 * tensor + stats}   # q k v -> o


def backward_dq(batch: int, heads: int, seq: int, head_dim: int,
                bytes_per_el: int = 2) -> Dict[str, float]:
    """Scores again, dP = dO v^T, dq = dS k: three products per pair."""
    flops = 3.0 * 2.0 * batch * heads * _pairs(seq) * head_dim
    tensor = batch * heads * seq * head_dim * bytes_per_el
    stats = 2 * batch * heads * seq * 4             # log-sum-exp and delta
    return {"flops": flops, "bytes": 5.0 * tensor + stats}  # q k v dO -> dq


def backward_dkv(batch: int, heads: int, seq: int, head_dim: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """Scores again, dv = P^T dO, dP = dO v^T, dk = dS^T q: four products."""
    flops = 4.0 * 2.0 * batch * heads * _pairs(seq) * head_dim
    tensor = batch * heads * seq * head_dim * bytes_per_el
    stats = 2 * batch * heads * seq * 4
    return {"flops": flops, "bytes": 6.0 * tensor + stats}  # q k v dO -> dk dv


def least_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bytes per second."""
    return max(cost["flops"] / peak["flops_bf16"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
