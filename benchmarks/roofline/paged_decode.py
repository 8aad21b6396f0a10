"""Operations and bytes of one paged-decode attention call: one query row
per head and slot against that slot's cached keys and values."""

from __future__ import annotations

from typing import Dict, Sequence


def call(cached_tokens: Sequence[int], heads: int, head_dim: int,
         bytes_per_el: int = 2) -> Dict[str, float]:
    """``cached_tokens[s]`` keys (the new one included) for each slot in use.
    Two products per key and head; every cached key and value is read once,
    the query read and the output written once."""
    keys = float(sum(cached_tokens))
    slots = len(cached_tokens)
    flops = 2.0 * 2.0 * keys * heads * head_dim
    kv = 2.0 * keys * heads * head_dim * bytes_per_el
    qo = 2.0 * slots * heads * head_dim * bytes_per_el
    return {"flops": flops, "bytes": kv + qo}
