"""What the serving engine asks of a model (:class:`ServeModel`), and the
one cast a model's ``served_params`` is made of (:func:`cast_once`).

Below the engine and beside :mod:`~horovod_tpu.serving.kv_cache`: a model
under ``horovod_tpu/models/`` builds its record from here (inside its
``cfg.serve_model()``, not at import: ``horovod_tpu.models`` loads nothing
of ``horovod_tpu.serving``) and never imports the engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from horovod_tpu.serving.kv_cache import CacheRows


@dataclasses.dataclass(frozen=True)
class ServeModel:
    """A model as the engine sees it. The engine owns slots, pages, block
    tables, the AOT/store plumbing and the program names; the model says
    what one token caches and what a step computes.

    ``decode(cfg, params, *pool, *state, block_tables, lengths, tokens)``
    and ``prefill(cfg, params, *pool, *state, block_table, start, n_real,
    tokens)`` return ``(*pool, *state, next_token(s), logits)``: ``pool``
    one array per :class:`~horovod_tpu.serving.kv_cache.CacheRows` of
    ``cache_rows(cfg)``, each ``[blocks, n_pages + 1, page, *row]``;
    ``state`` the further device arrays of ``state(cfg)`` (running
    counters), donated and handed on like the pool but left to the
    compiler's layout. ``check(cfg, draft_mode)`` refuses what the model
    cannot serve, in ``ValueError``'s words; ``stats(cfg, state)`` is
    what ``ServeEngine.stats()`` publishes of ``state`` (the one place
    it is read back). ``draft`` (the decode body over the first
    ``n_layers`` layers) only where the model offers ``truncate:N``.

    ``slot_state(cfg, slots)``: what a model with recurrent layers caches
    per SLOT instead of per token, as shapes over the number of slots. The
    engine allocates it zeroed where the pool lives, donates it to every
    step and hands it on after ``state`` (``stats`` is given both); the
    bodies update it in place. A model that declares any has its prefill
    told the slot: ``prefill(cfg, params, *pool, *state, *slot_state,
    block_table, slot, start, n_real, tokens)``, which starts from zeros
    at ``start == 0`` (nobody clears a released slot) and from what the
    chunk before stored otherwise; its ``decode`` advances the slots with
    ``lengths > 0`` and keeps every other slot's state (a slot mid-prefill
    is shown with length 0). Such state cannot be shared by prefix or
    rolled back: the engine refuses the prefix cache, the draft modes and
    ``rollback`` for the model.

    ``served_params(cfg, placed)`` is the tree the programs read, made
    once at engine build from the tree as placed on the device: the
    same structure, each leaf either the placed array itself or a copy
    of it in another dtype under the same sharding (:func:`cast_once`).
    Absent, the programs read the tree as given."""
    check: Callable[[Any, str], None]
    cache_rows: Callable[[Any], Tuple[CacheRows, ...]]
    decode: Callable[..., Tuple]
    prefill: Callable[..., Tuple]
    param_specs: Callable[[Any], Any]
    served_params: Optional[Callable[[Any, Any], Any]] = None
    state: Callable[[Any], Tuple[jax.ShapeDtypeStruct, ...]] = lambda cfg: ()
    slot_state: Callable[[Any, int], Tuple[jax.ShapeDtypeStruct, ...]] = (
        lambda cfg, slots: ())
    stats: Optional[Callable[[Any, Tuple], Dict[str, Any]]] = None
    draft: Optional[Callable[..., Tuple]] = None


def cast_once(x: Any, dtype: Any) -> Any:
    """``x`` in ``dtype`` where it lives: the array itself when it is in
    ``dtype`` already (no copy), else one cast on the device that keeps
    its sharding. ``x`` is neither donated nor deleted. A
    ``ShapeDtypeStruct`` (what a compile-only lowering has of a tree) is
    answered with one."""
    if x.dtype == dtype:
        return x
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(x.shape, dtype, sharding=x.sharding)
    return jax.jit(lambda a: a.astype(dtype), out_shardings=x.sharding)(x)
