"""Multi-head latent attention (MLA) over a paged latent cache, as the serving
engine runs it: the one attention block of the latent-attention models
(``models/longcat_flash.py``, two blocks a layer; ``models/kimi_k2.py``, one)
and the step both serve on.

An attention block on normed rows ``x`` (low-rank q and kv, no biases;
``N`` = RMSNorm, scale only)::

    cq = N(x Wqa);  [q_nope | q_rope] = (cq Wqb) a_q         per head
    [c | k_r] = x Wkva;  c = N(c) a_kv
    k_r = rope(k_r), one rotary key shared by every head;  q_rope = rope(q_rope)
    [k_nope | v] = c Wkvb                                    per head
    p = causal softmax((q_nope k_nope + q_rope k_r) * softmax_scale)   float32
    out = concat_h(p v) Wo

What a model's config answers: ``a_q`` and ``a_kv`` (LongCat scales its
latents, a model that does not says 1), ``softmax_scale`` (``1 / sqrt(nope +
rope)``, times YaRN's ``mscale^2`` where the rotary is stretched),
``rope_theta`` and ``rope_scaling`` (``transformer.RopeScaling`` or None:
:func:`~horovod_tpu.models.transformer.rope` stays the package's one
rotary).

**The cache holds, per token and attention block, ``c`` (after norm and
scale) and ``k_r`` (after rotary)**: ``kv_lora_rank + rope`` numbers, one
pool array of one latent row a token (:func:`cache_rows`). A decode step
absorbs ``Wkvb`` (``q_lat = q_nope Wkvb_k^T``, ``out = (p c) Wkvb_v``:
:func:`mla_attend_absorbed`) and reads only the latent rows, each slot's in
place and only the pages up to its length, through the paged kernel
``hvd_mla_decode`` (:func:`mla_attend_paged_decode`); where no kernel runs,
over each slot's gathered pages, every one of its ``max_seq`` positions
under a mask of those it may see (the kernel's spec). A prefill chunk
absorbs ``Wkvb`` too and reads the pages in place, only those the chunk can
see, through the paged kernel ``hvd_mla_prefill`` (:func:`mla_attend_paged`);
where no kernel runs, it expands ``k_nope`` and ``v`` from the gathered
``c`` (:func:`mla_attend_expanded`, scope ``hvd_mla_expand``: the kernel's
spec).

The step (:func:`decode_body`, :func:`prefill_body`): embed the tokens, the
model's layers through ``stack`` (which calls back for each attention block
with the block's index in the pool), the final norm and the head (a slice of
the vocabulary, untied), argmax; the routing counters of the expert layers
handed on."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import _rmsnorm, rope, visible_softmax
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]


def norm(cfg: Any, x: jax.Array, scale: jax.Array) -> jax.Array:
    """RMSNorm of the residual stream (float32) or of a latent, in float32;
    the result in the dtype of ``x``."""
    return _rmsnorm(x, scale, eps=cfg.norm_eps)


def cache_rows(cfg: Any):
    """One pool array of latent rows, ``cfg.attention_blocks`` blocks."""
    from horovod_tpu.serving.kv_cache import CacheRows
    return (CacheRows("latent", cfg.attention_blocks, (cfg.cache_row,)),)


def mla_project(cfg: Any, bp: Params, x: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The low-rank projections of one attention block on normed rows x
    ``[N, D]`` at positions ``pos``: (q_nope ``[N, H, nope]``, q_rope
    ``[N, H, rope]`` rotated, the cache row ``[N, kv_lora_rank + rope]`` =
    ``c`` after norm and scale beside ``k_r`` after rotary)."""
    dt = cfg.dtype
    n = x.shape[0]
    cq = norm(cfg, x @ bp["wq_a"].astype(dt), bp["q_norm"])
    q = ((cq @ bp["wq_b"].astype(dt)) * cfg.a_q).astype(dt)
    q = q.reshape(n, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    kv = x @ bp["wkv_a"].astype(dt)
    c = (norm(cfg, kv[:, :cfg.kv_lora_rank], bp["kv_norm"])
         * cfg.a_kv).astype(dt)
    k_r = rope(kv[:, cfg.kv_lora_rank:], pos, cfg.rope_theta, heads=0,
               scaling=cfg.rope_scaling)
    q_rope = rope(q_rope, pos, cfg.rope_theta, scaling=cfg.rope_scaling)
    return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1)


def _wkv_b(cfg, bp):
    """``Wkvb`` by head: (``[rank, H, nope]`` to keys, ``[rank, H, v]`` to
    values)."""
    w = bp["wkv_b"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def mla_attend_absorbed(cfg: Any, bp: Params, q_nope: jax.Array,
                        q_rope: jax.Array, rows: jax.Array,
                        visible: jax.Array) -> jax.Array:
    """Decode's attention: each query row n over ITS OWN cached rows
    ``rows[n]`` ``[T, kv_lora_rank + rope]`` with ``Wkvb`` absorbed into the
    query and the output, so what is read per cached token is the latent
    row and never the heads' keys and values. Returns ``[N, H * v]``."""
    dt = cfg.dtype
    scale = cfg.softmax_scale
    wk, wv = _wkv_b(cfg, bp)
    c, k_r = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
    with jax.named_scope("hvd_mla_proj"):
        q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, wk).astype(dt)
    with jax.named_scope("hvd_attention"):
        s = (jnp.einsum("nhr,ntr->nht", q_lat, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhd,ntd->nht", q_rope, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = visible_softmax(s, visible).astype(dt)
        o_lat = jnp.einsum("nht,ntr->nhr", p, c).astype(dt)
    with jax.named_scope("hvd_mla_proj"):
        o = jnp.einsum("nhr,rhv->nhv", o_lat, wv)
    return o.reshape(o.shape[0], -1).astype(dt)


def mla_attend_expanded(cfg: Any, bp: Params, q_nope: jax.Array,
                        q_rope: jax.Array, rows: jax.Array,
                        visible: jax.Array) -> jax.Array:
    """Prefill's attention: every query row over ONE sequence's cached rows
    ``[T, kv_lora_rank + rope]``, the heads' keys and values expanded from
    ``c`` (under ``hvd_mla_expand``: all ``T`` rows, whatever the mask).
    Returns ``[N, H * v]``."""
    dt = cfg.dtype
    scale = cfg.softmax_scale
    wk, wv = _wkv_b(cfg, bp)
    c, k_r = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:]
    with jax.named_scope("hvd_mla_expand"):
        k_nope = jnp.einsum("tr,rhd->thd", c, wk).astype(dt)
        v = jnp.einsum("tr,rhv->thv", c, wv).astype(dt)
    with jax.named_scope("hvd_attention"):
        s = (jnp.einsum("nhd,thd->nht", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhd,td->nht", q_rope, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = visible_softmax(s, visible).astype(dt)
        o = jnp.einsum("nht,thv->nhv", p, v)
    return o.reshape(o.shape[0], -1).astype(dt)


def _absorbed_through(cfg: Any, bp: Params, q_nope: jax.Array,
                      q_rope: jax.Array, kernel: Callable) -> jax.Array:
    """``Wkvb`` absorbed into the query rows ``[N*H, rank + rope]`` that a
    paged latent kernel takes (``kernel(q)`` -> ``o_lat`` ``[N*H, rank]``,
    under ``hvd_attention``) and into its output: returns ``[N, H * v]``."""
    dt = cfg.dtype
    n, h = q_nope.shape[:2]
    wk, wv = _wkv_b(cfg, bp)
    with jax.named_scope("hvd_mla_proj"):
        q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, wk).astype(dt)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
    with jax.named_scope("hvd_attention"):
        o_lat = kernel(q.reshape(n * h, -1))
    with jax.named_scope("hvd_mla_proj"):
        o = jnp.einsum("nhr,rhv->nhv", o_lat.reshape(n, h, -1), wv)
    return o.reshape(n, -1).astype(dt)


def mla_attend_paged(cfg: Any, bp: Params, q_nope: jax.Array,
                     q_rope: jax.Array, flat: jax.Array, bt: jax.Array,
                     start: jax.Array, n_real: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Prefill's attention through the paged kernel
    (``ops/pallas/mla_prefill``): ``Wkvb`` absorbed as in decode, every
    head of the chunk's rows against ONE sequence's cached rows, read in
    place from the flat pool ``flat`` through its block table ``bt`` and
    only as far as the chunk can see. What :func:`mla_attend_expanded`
    computes over the gathered pages; returns ``[N, H * v]``."""
    from horovod_tpu.ops.pallas import mla_prefill
    return _absorbed_through(
        cfg, bp, q_nope, q_rope, lambda q: mla_prefill.mla_prefill(
            q, flat, bt, start, n_real, heads=q_nope.shape[1],
            rank=cfg.kv_lora_rank, scale=cfg.softmax_scale,
            interpret=interpret))


def mla_attend_paged_decode(cfg: Any, bp: Params, q_nope: jax.Array,
                            q_rope: jax.Array, flat: jax.Array,
                            bt: jax.Array, lengths: jax.Array,
                            interpret: bool = False) -> jax.Array:
    """Decode's attention through the paged kernel
    (``ops/pallas/mla_decode``): each slot's heads against its own cached
    rows, read in place from the flat pool through its block table ``bt``
    ``[N, n_max]``, positions ``0 .. lengths[n]`` only. What
    :func:`mla_attend_absorbed` computes over the gathered pages; returns
    ``[N, H * v]``."""
    from horovod_tpu.ops.pallas import mla_decode
    return _absorbed_through(
        cfg, bp, q_nope, q_rope, lambda q: mla_decode.mla_decode(
            q, flat, bt, lengths, rank=cfg.kv_lora_rank,
            scale=cfg.softmax_scale,
            interpret=interpret))


def _gathered(attend_rows: Callable) -> Callable:
    """``attend_rows`` over each sequence's pages gathered whole (under
    ``hvd_attention``), every position of its block table under the mask
    ``visible``: the step's attention as ``_serve_step`` calls it."""
    def attend(cfg, bp, q_nope, q_rope, flat, bt, visible):
        from horovod_tpu.serving import kv_cache as kvc
        with jax.named_scope("hvd_attention"):
            rows = kvc.gather_pages(flat, bt)   # [(N,) n_ctx, row]
        return attend_rows(cfg, bp, q_nope, q_rope, rows, visible)
    return attend


def logits_of(cfg: Any, params: Params, h: jax.Array) -> jax.Array:
    """``N(h) head`` over the rows of the vocabulary held here, float32."""
    x = norm(cfg, h, params["final_norm"]).astype(cfg.dtype)
    return jnp.dot(x, params["head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the serving engine's step bodies (serving.model.ServeModel)
# ---------------------------------------------------------------------------

# ``stack(cfg, layers, h, flat, total, attend, counted)`` -> (h, flat,
# total): a model's layers on the float32 residual stream h ``[N, D]``, each
# attention block through ``attend(flat, block, bp, x)`` -> (its output
# ``[N, H * v]`` before ``Wo``, flat), ``block`` its index in the pool; the
# routing counters of the rows ``counted`` added to ``total``.
Stack = Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]


def _serve_step(cfg: Any, params: Params, pool: jax.Array,
                counters: jax.Array, block_tables: jax.Array,
                tokens: jax.Array, pos: jax.Array, counted: jax.Array,
                write, mla_attend, program: int, stack: Stack,
                out_row: Optional[jax.Array] = None):
    """What a decode step and a prefill chunk share: embed ``tokens``
    ``[N]``, the layers at positions ``pos`` ``[N]`` through the latent
    cache (pool ``[blocks, P+1, page, row]``), each block writing its rows
    through ``write(pages, new, block_tables, scratch)`` and attending with
    ``mla_attend(cfg, bp, q_nope, q_rope, flat, block_tables, visible)``
    over the flat pool, each row seeing the cached positions up to its own
    (``visible``, over the block tables' positions); the head (of row
    ``out_row`` only, if given), argmax. The rows ``counted`` go into
    ``program``'s routing counters."""
    from horovod_tpu.serving import kv_cache as kvc
    n_ctx = block_tables.shape[-1] * pool.shape[2]
    visible = jnp.arange(n_ctx, dtype=jnp.int32)[None, :] <= pos[:, None]
    h = params["embed"][tokens].astype(jnp.float32)                 # [N, D]
    flat, = kvc.flat_pool(pool)

    def attend(flat, block, bp, x):
        bt, scratch = kvc.block_pages(pool.shape, block, block_tables)
        with jax.named_scope("hvd_mla_proj"):
            q_nope, q_rope, row = mla_project(cfg, bp, x, pos)
        with jax.named_scope("hvd_kv_write"):
            flat, = write((flat,), (row,), bt, scratch)
        return mla_attend(cfg, bp, q_nope, q_rope, flat, bt, visible), flat

    zero = jnp.zeros((counters.shape[-1],), jnp.int32)
    h, flat, total = stack(cfg, params["layers"], h, flat, zero, attend,
                           counted)
    if out_row is not None:
        h = jnp.take(h, out_row, axis=0)                            # [D]
    logits = logits_of(cfg, params, h)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (flat.reshape(pool.shape),
            moe_lib.add_share_counts(counters, total, program),
            next_tokens, logits)


def _kernel_mode(cfg: Any) -> Optional[bool]:
    """Whether the step bodies attend through the paged kernels: None where
    ``flash_attention.enabled()`` or the kernels' ``supports`` say no, else
    whether they run interpreted."""
    from horovod_tpu.ops.pallas import flash_attention as fa, mla_prefill
    mode = fa.enabled()
    if mode and mla_prefill.supports(cfg.dtype, cfg.kv_lora_rank,
                                     interpret=mode == "interpret"):
        return mode == "interpret"
    return None


def decode_body(cfg: Any, params: Params, pool: jax.Array,
                counters: jax.Array, block_tables: jax.Array,
                lengths: jax.Array, tokens: jax.Array, *, stack: Stack):
    """One decode step over all slots through the latent cache, ``Wkvb``
    absorbed (each slot over its own pages). Empty slots carry length 0
    and scratch block tables; their rows sink into the scratch page and
    are not counted (a served slot has its prompt cached). The attention is
    the paged kernel's (:func:`mla_attend_paged_decode`) where
    :func:`_kernel_mode` allows it, else the gathered pages under the mask
    (:func:`mla_attend_absorbed`, the kernel's spec)."""
    from horovod_tpu.serving import kv_cache as kvc
    valid = lengths < block_tables.shape[1] * pool.shape[2]

    def write(pages, new, bt, scratch):
        return kvc.write_token_rows(pages, new, bt, lengths, valid=valid,
                                    scratch=scratch)

    interpret = _kernel_mode(cfg)
    if interpret is not None:
        def attend(cfg, bp, q_nope, q_rope, flat, bt, visible):
            return mla_attend_paged_decode(cfg, bp, q_nope, q_rope, flat, bt,
                                           lengths, interpret=interpret)
    else:
        attend = _gathered(mla_attend_absorbed)
    return _serve_step(cfg, params, pool, counters, block_tables, tokens,
                       lengths, lengths > 0, write, attend, moe_lib.DECODE,
                       stack)


def prefill_body(cfg: Any, params: Params, pool: jax.Array,
                 counters: jax.Array, block_table: jax.Array,
                 start: jax.Array, n_real: jax.Array, tokens: jax.Array, *,
                 stack: Stack):
    """One prefill chunk of ONE sequence: tokens ``[C]`` (bucket-padded) at
    positions ``start ..``, their latent rows written to the pages, causal
    attention over the cached prefix + the chunk, the last real token's
    logits out. The attention is the paged kernel's
    (:func:`mla_attend_paged`) where :func:`_kernel_mode` allows it, else
    keys and values expanded from the gathered rows
    (:func:`mla_attend_expanded`, the kernel's spec)."""
    from horovod_tpu.serving import kv_cache as kvc
    c = tokens.shape[0]
    pos = start + jnp.arange(c, dtype=jnp.int32)

    def write(pages, new, bt, scratch):
        return kvc.write_chunk_rows(pages, new, bt, start, n_real,
                                    scratch=scratch)

    interpret = _kernel_mode(cfg)
    if interpret is not None:
        def attend(cfg, bp, q_nope, q_rope, flat, bt, visible):
            return mla_attend_paged(cfg, bp, q_nope, q_rope, flat, bt, start,
                                    n_real, interpret=interpret)
    else:
        attend = _gathered(mla_attend_expanded)
    return _serve_step(cfg, params, pool, counters, block_table, tokens,
                       pos, jnp.arange(c) < n_real, write, attend,
                       moe_lib.PREFILL, stack,
                       out_row=jnp.maximum(n_real - 1, 0))
