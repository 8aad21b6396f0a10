"""The serving engine: slots, pages, block tables and AOT-compiled step
programs for whatever model hands it step bodies and cache rows
(``cfg.serve_model()`` -> :class:`~horovod_tpu.serving.model.ServeModel`;
the dense ``TransformerConfig``, ``LongCatFlashConfig`` and
``GraniteHybridConfig`` do, from ``horovod_tpu/models/``). The engine holds
no model: it names no parameter leaf and no device scope, and computes
nothing of a layer.

Around a paged cache (:mod:`serving.kv_cache`) it builds exactly TWO
compiled program families from the model's bodies —

- **prefill**: one sequence, one chunk of its prompt at a fixed bucket
  length (powers of two up to ``HOROVOD_SERVE_PREFILL_CHUNK``), the chunk's
  cache rows written into the sequence's pages, logits of the last real
  token out;
- **decode**: ONE token for every batch slot at once
  (``HOROVOD_SERVE_SLOTS`` fixed), each slot attending over its own
  pages (the same program at batch ``slots * (K+1)`` is the speculative
  verify step; over a model's first layers, its ``truncate:N`` draft).

Tokens go from program to program on the device: the engine keeps every
slot's newest token in one ``[slots] int32`` vector there, a decode step
reads it and its result replaces it, and a prompt's first token is put
into it by a third, model-independent program (``hvd_serve_token``). So a
step can be queued before the host has read the one before
(:meth:`ServeEngine.decode_step`).

Every variant is AOT-compiled at engine boot and served through the
PR 12 artifact store under the ``serve`` kind, so a warm replica
reaches its first token with ZERO builder invocations
(``ServeEngine.builds`` — the BENCH_TTFS warm-boot story applied to
serving). Shapes are static by construction: no request, prompt length
or batch occupancy can trigger a compile after boot.

Tensor parallelism: when ``cfg.tp_axis`` is set a whole step runs
inside ``shard_map`` under the model's ``param_specs``; the page pool is
sharded over the axis its :class:`~horovod_tpu.serving.kv_cache.CacheRows`
name (the dense block's: KV heads), so each shard pages only its own.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, \
    Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from horovod_tpu import tracing as trace
from horovod_tpu.config import knobs
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import kv_cache as kvc
from horovod_tpu.serving.model import ServeModel, cast_once  # noqa: F401
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.logging import get_logger

logger = get_logger("horovod_tpu.serving")

# The dense step bodies under the names they had while they lived here:
# tests/benchmark/test_bench_real_size_compiles.py and bench.py (--verify,
# --cost-report) import them from this module (ROADMAP S12 and D1 end that).
_decode_body = tfm.decode_body
_prefill_body = tfm.prefill_body


def prefill_buckets(chunk_cap: Optional[int] = None) -> List[int]:
    """Fixed prefill bucket lengths: powers of two from 32 up to
    HOROVOD_SERVE_PREFILL_CHUNK — ONE compiled executable per bucket,
    every prompt padded up to its bucket, no length ever compiles."""
    cap = int(chunk_cap or knobs.get("HOROVOD_SERVE_PREFILL_CHUNK"))
    out, b = [], 32
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return out


def _parse_draft(spec: str, n_layers: int) -> Tuple[str, int]:
    """HOROVOD_SERVE_DRAFT -> (mode, n): 'off', 'ngram[:N]' (host-side
    n-gram drafter, N = match order, default 3), or 'truncate:N'
    (self-drafting from the target's first N layers)."""
    s = str(spec or "off").strip().lower()
    if s in ("", "off", "0"):
        return "off", 0
    head, _, arg = s.partition(":")
    if head == "ngram":
        n = int(arg or 3)
        if n < 1:
            raise ValueError(
                f"HOROVOD_SERVE_DRAFT={spec!r}: n-gram order must be "
                f">= 1")
        return "ngram", n
    if head == "truncate":
        if not arg:
            raise ValueError(
                f"HOROVOD_SERVE_DRAFT={spec!r}: truncate needs a layer "
                f"count, e.g. 'truncate:2'")
        n = int(arg)
        if not (1 <= n < n_layers):
            raise ValueError(
                f"HOROVOD_SERVE_DRAFT={spec!r}: draft layer count must "
                f"be in [1, {n_layers - 1}] (the target has "
                f"{n_layers} layers; drafting with all of them is just "
                f"decoding twice)")
        return "truncate", n
    raise ValueError(
        f"HOROVOD_SERVE_DRAFT={spec!r}: expected 'off', 'ngram[:N]' "
        f"or 'truncate:N'")


def serve_model(cfg: Any) -> ServeModel:
    """What ``cfg``'s model hands the engine: its ``cfg.serve_model()``."""
    own = getattr(cfg, "serve_model", None)
    if own is None:
        raise TypeError(
            f"serving needs a config with a serve_model() of its own (as "
            f"TransformerConfig, LongCatFlashConfig, GraniteHybridConfig and "
            f"SolarOpen2Config have), got {type(cfg).__name__}")
    return own()


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def _named_jit(fn: Callable, name: str, pool_args: Tuple[int, ...],
               n_args: int, n_out: int, fmts: Sequence[Format],
               state_args: Tuple[int, ...] = ()):
    """``jax.jit`` of a step function under a name of its own: the
    compiled module, and so the ``XLA Modules`` line of a device trace,
    reads ``jit_<name>`` where a ``functools.partial`` or a ``shard_map``
    wrapper would read ``jit__unknown`` — what a trace reader tells the
    engine's programs apart by.

    Every engine program takes the pool (the dense block's: K, V) at
    ``pool_args``, donated, and returns it first; both sides are pinned
    to the pool's ``fmts`` (one for each of its arrays) so no program
    converts the pool's layout on its way in or out. A model's
    further state (``state_args``) is donated too and follows the pool.
    The other arguments and results are left to the compiler."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    fmts = tuple(fmts)
    at = dict(zip(pool_args, fmts))
    return jax.jit(
        named, donate_argnums=pool_args + state_args,
        in_shardings=tuple(at.get(i) for i in range(n_args)),
        out_shardings=fmts + (None,) * (n_out - len(fmts)))


def serve_programs(cfg: Any, fmt: Union[Format, Sequence[Format]],
                   mesh: Optional[Mesh] = None,
                   draft_layers: Optional[int] = None
                   ) -> Dict[str, Callable]:
    """The engine's jitted program families over a pool held in ``fmt``
    (one format for every array of the pool, or one each): ``decode``
    (also the verify step, at another batch), ``prefill`` (every
    bucket), ``cow`` and, with ``draft_layers``, ``draft``. Shard_map'd
    over ``mesh`` when ``cfg.tp_axis`` is set, plain otherwise."""
    model = serve_model(cfg)
    n_pool = len(model.cache_rows(cfg))
    n_slot = len(model.slot_state(cfg, 1))
    held = n_pool + len(model.state(cfg)) + n_slot
    fmts = (fmt,) * n_pool if isinstance(fmt, Format) else tuple(fmt)
    # name -> (function, where the pool's first array stands among its
    # arguments (the others and the state follow; the parameters lead
    # when that is 1), arguments, results)
    table = {"decode": (functools.partial(model.decode, cfg), 1,
                        held + 4, held + 2),
             # a model with per-slot state has its prefill told the slot
             "prefill": (functools.partial(model.prefill, cfg), 1,
                         held + 5 + bool(n_slot), held + 2),
             "cow": (kvc.copy_page, 0, n_pool + 2, n_pool)}
    if draft_layers:
        table["draft"] = (functools.partial(model.draft, cfg, draft_layers),
                          1, held + 4, held + 2)
    programs = {}
    for name, (fn, k_at, n_args, n_out) in table.items():
        pool_args = tuple(range(k_at, k_at + n_pool))
        if cfg.tp_axis and mesh is not None:
            from horovod_tpu.eager import shard_map
            kv = tuple(f.sharding.spec for f in fmts)
            in_specs = [P()] * n_args
            in_specs[k_at:k_at + n_pool] = kv
            if k_at:
                in_specs[0] = model.param_specs(cfg)
            fn = shard_map(fn, mesh, in_specs=tuple(in_specs),
                           out_specs=kv + (P(),) * (n_out - n_pool))
        programs[name] = _named_jit(
            fn, f"hvd_serve_{name}", pool_args, n_args, n_out, fmts,
            state_args=(tuple(range(k_at + n_pool, k_at + held))
                        if k_at else ()))
    return programs


def hvd_serve_token(newest: jax.Array, token: jax.Array,
                    slot: jax.Array) -> jax.Array:
    """``newest`` with ``token`` at ``slot``: how a prompt's first token
    reaches the next decode step without passing through the host (the
    compiled module reads ``jit_hvd_serve_token``)."""
    return newest.at[slot].set(token)


def _abstract(x: Any) -> Any:
    """Shape, dtype and placement of a live array, for lowering."""
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def _i32(*shape: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.int32)


class ServeEngine:
    """Paged-cache inference engine over a (possibly TP-sharded) mesh.

    Owns the device state (page pools), the host-side allocator/block
    tables, and the AOT-compiled prefill/decode executables; the
    continuous-batching policy lives in ``serving.scheduler``. Slot
    operations (``prefill``/``decode_step``/``release``) are the
    step-boundary API the scheduler drives.
    """

    def __init__(self, cfg: Any, params: Any,
                 mesh: Optional[Mesh] = None, *,
                 slots: Optional[int] = None,
                 page: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft: Optional[str] = None,
                 spec_k: Optional[int] = None):
        self.model = model = serve_model(cfg)
        self.draft_spec = str(
            knobs.get("HOROVOD_SERVE_DRAFT") if draft is None else draft)
        self.draft_mode, self.draft_n = _parse_draft(
            self.draft_spec, cfg.n_layers)
        model.check(cfg, self.draft_mode)
        # recurrent state a slot: neither shared by prefix nor rolled back
        self.slot_stateful = bool(model.slot_state(cfg, 1))
        # a replica need not go through hvd.init(): HOROVOD_TRACE=1 turns
        # the recorder on here too (a no-op when it is on already)
        trace.init_from_env()
        self.cfg = cfg
        self.mesh = mesh
        self.slots = int(slots or knobs.get("HOROVOD_SERVE_SLOTS"))
        self.page = int(page or knobs.get("HOROVOD_SERVE_PAGE"))
        requested_ms = int(max_seq or knobs.get("HOROVOD_SERVE_MAX_SEQ"))
        self.max_seq = min(requested_ms, cfg.max_seq)
        # Which limit actually binds: error messages must send the
        # operator to a lever that can move it, and raising the knob
        # does nothing when the model's trained context is smaller.
        self.ceiling_hint = (
            f"cfg.max_seq={cfg.max_seq} (the model's trained context)"
            if cfg.max_seq < requested_ms else "HOROVOD_SERVE_MAX_SEQ")
        self.n_max_pages = -(-self.max_seq // self.page)
        pool_pages = int(n_pages or knobs.get("HOROVOD_SERVE_PAGES")) \
            or self.slots * self.n_max_pages
        self.buckets = prefill_buckets(prefill_chunk)
        self.prefix_cache = bool(
            knobs.get("HOROVOD_SERVE_PREFIX_CACHE")
            if prefix_cache is None else prefix_cache)
        self.spec_k = (int(spec_k if spec_k is not None
                           else knobs.get("HOROVOD_SERVE_SPEC_K"))
                       if self.draft_mode != "off" else 0)
        if self.draft_mode != "off" and self.spec_k < 1:
            raise ValueError(
                f"HOROVOD_SERVE_DRAFT={self.draft_spec!r} needs "
                f"HOROVOD_SERVE_SPEC_K >= 1 drafts per step, got "
                f"{self.spec_k}")
        if self.slot_stateful and (self.prefix_cache
                                   or self.draft_mode != "off"):
            raise ValueError(
                f"{type(cfg).__name__} keeps recurrent state per slot, "
                f"which serves with the prefix cache off and plain decode "
                f"only; got prefix_cache={self.prefix_cache}, draft "
                f"{self.draft_spec!r}. Adopted pages would skip prompt "
                f"tokens the recurrent layers have to see, and a rejected "
                f"draft would have advanced a state nothing can roll back. "
                f"Set HOROVOD_SERVE_PREFIX_CACHE=0 and "
                f"HOROVOD_SERVE_DRAFT=off.")

        tp = cfg.tp_axis
        self._tp_size = int(mesh.shape[tp]) if (tp and mesh) else 1
        if tp and cfg.n_heads % self._tp_size:
            raise ValueError(
                f"n_heads={cfg.n_heads} not divisible by tp="
                f"{self._tp_size}")

        rows = model.cache_rows(cfg)
        self.pool = kvc.PagePool(rows[0].blocks, pool_pages, self.page,
                                 dtype=cfg.dtype, rows=rows)
        self.allocator = kvc.PageAllocator(pool_pages)
        self.tables = kvc.BlockTables(self.slots, self.n_max_pages,
                                      self.pool.scratch_page)
        self.slot_pages: List[Optional[List[int]]] = [None] * self.slots
        # shared-prefix reuse: tokens of each slot's prompt the index
        # already covered (the scheduler starts prefill there)
        self.prefix = (kvc.PrefixIndex(self.page, self.allocator)
                       if self.prefix_cache else None)
        self.slot_skip: List[int] = [0] * self.slots
        self.cow_copies = 0

        # device placement: on the mesh when one is given — pages sharded
        # over KV heads under TP, otherwise params and pages replicated
        # over it (a one-device mesh pins the replica to that chip);
        # without a mesh, on the device the caller's params already live.
        if tp and mesh is not None:
            def over_tp(r: kvc.CacheRows) -> NamedSharding:
                spec = [None] * (3 + len(r.row))
                if r.tp_axis is not None:
                    spec[3 + r.tp_axis] = tp
                return NamedSharding(mesh, P(*spec))
            kv_shardings = [over_tp(r) for r in rows]
            pspecs = model.param_specs(cfg)
            placed = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)))
        elif mesh is not None:
            kv_shardings = [NamedSharding(mesh, P())] * len(rows)
            placed = jax.device_put(params, kv_shardings[0])
        else:
            placed = jax.tree.map(jnp.asarray, params)
            kv_shardings = [SingleDeviceSharding(
                next(iter(jax.tree.leaves(placed)[0].devices())))] * len(rows)
        # The tree the programs read is made once, here: the leaves the
        # model names cast to ``cfg.dtype`` on the device, every other
        # leaf (and any in ``cfg.dtype`` already) the placed array
        # itself, so no program reads or converts a float32 weight
        # stack. The engine keeps no reference to a leaf it replaced:
        # what the caller drops of its own tree is freed.
        self.params, self.weights = self._served_params(placed)
        del placed
        # the pool's one layout and placement: allocated in it, and
        # pinned on every program that takes or returns the pool
        self.pool_formats = tuple(
            kvc.pool_format(sh, 3 + len(r.row))
            for sh, r in zip(kv_shardings, rows))
        self.pool_format = self.pool_formats[0]
        # Where a reloaded executable loses the pinned layout of its
        # results, every program that makes or returns the pool is
        # compiled in this process: past JAX's persistent cache and
        # past the artifact store.
        from horovod_tpu.store import artifact_store as store_mod
        probe_row = list(rows[0].row)
        if rows[0].tp_axis is not None:
            probe_row[rows[0].tp_axis] //= self._tp_size
        self.reload_keeps_layout = store_mod.reload_keeps_layout(
            self.pool_format, (1, 1, self.page, *probe_row), cfg.dtype)
        if not self.reload_keeps_layout:
            logger.warning(
                "serve: a reloaded executable does not keep the KV "
                "pool's pinned layout on this backend; compiling the "
                "engine's programs in process (no persistent compile "
                "cache, no artifact store) at every engine build")
        programs = serve_programs(
            cfg, self.pool_formats, mesh,
            self.draft_n if self.draft_mode == "truncate" else None)
        self._decode_jit = programs["decode"]
        self._prefill_jit = programs["prefill"]
        self._draft_jit = programs.get("draft")
        self._cow_jit = programs["cow"]
        self.builds = 0
        self.store_outcomes: Dict[str, str] = {}
        # temporaries of each compiled program (memory_analysis): a
        # program that copied or re-laid the pool would read pool-sized
        self.program_temp_bytes: Dict[str, int] = {}
        # and its arguments as they lie on one device: what the decode
        # program's hold beyond weights and state is the pool, padding
        # of its rows' lanes included (``stats()["pool"]``)
        self.program_argument_bytes: Dict[str, int] = {}
        self._dispatch: Dict[str, Callable] = {}
        # decode steps enqueued; those enqueued while the step before was
        # still unread; and how often the host read a step with nothing
        # queued behind it, by what made it
        self.decode_counts: Dict[str, Any] = {
            "steps": 0, "dispatched_ahead": 0, "drained": {}}
        # prefill chunks; the pages a chunk's attention can see (its cached
        # prefix and itself: what the latent models' paged prefill kernel
        # walks, ``ops/pallas/mla_prefill``) and its block table's width
        # (what a gather over the table reads), summed over chunks
        self.prefill_pages: Dict[str, int] = {
            "chunks": 0, "walked": 0, "table": 0}
        # decode steps; the pages each live slot's attention can see (its
        # cached tokens and the new one: what the latent models' paged decode
        # kernel walks, ``ops/pallas/mla_decode``) and every slot's block
        # table (what a gather over the tables reads), summed over steps
        self.decode_pages: Dict[str, int] = {
            "steps": 0, "walked": 0, "table": 0}
        with (contextlib.nullcontext() if self.reload_keeps_layout
              else compile_cache.uncached()):
            self._build()
        _register_engine(self)
        # fixed once the decode program is built: read again by stats()
        self.pool_held = held = self._pool_held()
        logger.info(
            "serve engine up: %d slots, %d+1 pages x %d tokens "
            "(%.1f MiB KV pool of rows %s, %.1f MiB a device as the "
            "decode program holds it: x %s), prefill buckets %s, tp=%d, "
            "builds=%d, "
            "decode temporaries %.1f MiB, weights %.1f MiB resident "
            "(%d leaves cast once from %.1f MiB)",
            self.slots, pool_pages, self.page,
            self.pool.nbytes() / 2 ** 20, held["rows"],
            (held["resident_bytes"] or 0) / 2 ** 20, held["padding"],
            self.buckets, self._tp_size, self.builds,
            self.program_temp_bytes.get("serve_decode", 0) / 2 ** 20,
            self.weights["resident_bytes"] / 2 ** 20,
            self.weights["cast_leaves"],
            self.weights["cast_from_bytes"] / 2 ** 20)

    def _served_params(self, placed: Any) -> Tuple[Any, Dict[str, int]]:
        """(the tree the programs read, what making it took): the
        model's ``served_params`` of the placed tree, or that tree where
        the model names nothing. ``cast_leaves`` counts the leaves that
        are no longer the placed array, ``cast_from_bytes`` what those
        held, ``resident_bytes`` what the served tree holds (whole
        arrays, whatever their sharding)."""
        served = placed
        if self.model.served_params is not None:
            with trace.span("engine.weights.prepare", cat=trace.CAT_SERVE):
                served = jax.block_until_ready(
                    self.model.served_params(self.cfg, placed))
        replaced = [a for a, b in zip(jax.tree.leaves(placed),
                                      jax.tree.leaves(served)) if b is not a]
        return served, {
            "cast_leaves": len(replaced),
            "cast_from_bytes": sum(int(a.nbytes) for a in replaced),
            "resident_bytes": sum(int(b.nbytes)
                                  for b in jax.tree.leaves(served))}

    # -- AOT/store plumbing --------------------------------------------------
    def _build(self) -> None:
        """Allocate the pool and AOT-build (store-served) one decode
        executable + one prefill executable per bucket — plus, when the
        knobs switch them on, the speculative verify step, the
        truncated-layer draft step, and the COW page copy. `builds`
        counts actual compiles — the warm-boot gate asserts it stays 0
        on a warm store, new executables included."""
        self.pools: Tuple[jax.Array, ...] = self.pool.alloc_arrays(
            self.pool_formats)
        # a model's further state: replicated where the pool lives
        sharding = self.pool_format.sharding
        if isinstance(sharding, NamedSharding):
            sharding = NamedSharding(sharding.mesh, P())
        # and its per-slot state, one buffer each for the engine's life:
        # every step takes it donated and returns it updated in place
        self.state: Tuple[jax.Array, ...] = tuple(
            jax.device_put(jnp.zeros(s.shape, s.dtype), sharding)
            for s in (*self.model.state(self.cfg),
                      *self.model.slot_state(self.cfg, self.slots)))
        # every slot's newest token, where the next decode step reads it;
        # and the decode step whose tokens the host has not read yet
        self._newest: jax.Array = jax.device_put(
            jnp.zeros((self.slots,), jnp.int32), sharding)
        self._unread: Optional[jax.Array] = None
        self._put_first = self._adopt(
            jax.jit(hvd_serve_token),
            (_abstract(self._newest), _i32(), _i32()), "serve_first_token")
        self._decode = self._adopt(
            self._decode_jit, self._decode_args(), "serve_decode")
        self._prefill: Dict[int, Callable] = {}
        for b in self.buckets:
            self._prefill[b] = self._adopt(
                self._prefill_jit, self._prefill_args(b),
                f"serve_prefill_{b}")
        self._verify = self._draft = self._cow = None
        if self.spec_k:
            # the decode body at batch slots*(K+1): each slot's
            # block-table row repeated K+1 times
            self._verify = self._adopt(
                self._decode_jit,
                self._decode_args(self.slots * (self.spec_k + 1)),
                f"serve_verify_k{self.spec_k}")
            if self._draft_jit is not None:
                self._draft = self._adopt(
                    self._draft_jit, self._decode_args(),
                    f"serve_draft_l{self.draft_n}")
        if self.prefix is not None:
            self._cow = self._adopt(
                self._cow_jit, self._cow_args(), "serve_cow_copy")

    # Programs are lowered from shapes: a concrete donated example would
    # have to be a second pool.
    def _pool_args(self) -> Tuple:
        return tuple(_abstract(a) for a in self.pools)

    def _held_args(self) -> Tuple:
        """The pool and the model's further state, as a step takes them."""
        return tuple(_abstract(a) for a in self.pools + self.state)

    def _decode_args(self, rows: Optional[int] = None) -> Tuple:
        rows = rows or self.slots
        return (jax.tree.map(_abstract, self.params), *self._held_args(),
                _i32(rows, self.n_max_pages), _i32(rows), _i32(rows))

    def _prefill_args(self, bucket: int) -> Tuple:
        return (jax.tree.map(_abstract, self.params), *self._held_args(),
                _i32(self.n_max_pages), *self._slot_arg(),
                _i32(), _i32(), _i32(bucket))

    def _slot_arg(self, slot: Optional[int] = None) -> Tuple:
        """The slot a prefill chunk fills (its shape, for lowering), for
        the model that is told."""
        if not self.slot_stateful:
            return ()
        return (_i32() if slot is None else jnp.asarray(slot, jnp.int32),)

    def _cow_args(self) -> Tuple:
        return (*self._pool_args(), _i32(), _i32())

    def _adopt(self, fn: Callable, args: Tuple, label: str) -> Callable:
        """AOT-compile `fn` for the abstract `args`, served from the
        artifact store (kind 'serve') when one is configured and a
        reloaded executable is whole (``reload_keeps_layout``; where
        not, outcome 'unsupported', and ``_build`` runs past JAX's
        persistent cache too); counts real compiles in
        ``self.builds``."""
        from horovod_tpu.store import artifact_store as store_mod
        if store_mod.enabled() and self.reload_keeps_layout:
            wrapped, outcome = store_mod.adopt_step(
                fn, args, label=label, kind="serve")
            if outcome != "hit":
                self.builds += 1
        else:
            compiled, dt = store_mod.aot_compile(fn, args)
            self.builds += 1
            outcome = "unsupported" if store_mod.enabled() else "disabled"
            logger.debug("serve: %s compiled in %.2fs (artifact store "
                         "%s)", label, dt, outcome)
            wrapped = store_mod.wrap_compiled(compiled, fn, label)
        self.store_outcomes[label] = outcome
        compiled = getattr(wrapped, "hvd_store_compiled", None)
        if compiled is not None:
            memory = compiled.memory_analysis()
            self.program_temp_bytes[label] = int(memory.temp_size_in_bytes)
            self.program_argument_bytes[label] = int(
                memory.argument_size_in_bytes)
        self._dispatch[label] = wrapped
        return wrapped

    # -- the device state a step takes and hands back ------------------------
    @property
    def k_pages(self) -> jax.Array:
        """The dense block's K pool (the pool's first array)."""
        return self.pools[0]

    @property
    def v_pages(self) -> jax.Array:
        """The dense block's V pool (the pool's second array)."""
        return self.pools[1]

    def _step(self, program: Callable, *args) -> Tuple:
        """Run a step program on the parameters, the pool and the
        model's state; keep what it hands back of the last two and
        return the rest (next tokens, logits)."""
        out = program(self.params, *self.pools, *self.state, *args)
        n_pool, held = len(self.pools), len(self.pools) + len(self.state)
        self.pools, self.state = tuple(out[:n_pool]), tuple(out[n_pool:held])
        return out[held:]

    def executable_text(self, label: str = "serve_decode") -> str:
        """Compiled HLO text of one of the engine's AOT executables (the
        labels of ``store_outcomes``) — what a caller inspects to see
        which kernels the step it dispatches really contains."""
        return self._dispatch[label].hvd_store_compiled.as_text()

    # -- slot API (driven by the scheduler at step boundaries) ---------------
    def reserve(self, n_tokens_worst_case: int,
                prompt: Optional[np.ndarray] = None) -> Optional[int]:
        """Free slot id with pages reserved for the worst case, or None
        (no slot / pool drained — admission waits). A worst case the
        block table cannot hold is a caller bug, not backpressure —
        the scheduler must clamp max_new_tokens to the context ceiling
        BEFORE reserving (an un-clamped request would decode past its
        last page and silently corrupt its own cache).

        With the prefix cache on and ``prompt`` given, the resident
        prefix is adopted instead of re-reserved: matched full pages go
        into the block table shared (one incref each), a partial-block
        divergence copy-on-writes its source page, and only the TAIL is
        newly allocated (LRU-evicting index-only pages if the free list
        is short). ``slot_skip[slot]`` then tells the scheduler how
        many prompt tokens to skip prefilling."""
        if n_tokens_worst_case > self.max_seq:
            raise ValueError(
                f"worst case of {n_tokens_worst_case} tokens exceeds "
                f"the serving context ceiling {self.max_seq} — clamp "
                f"max_new_tokens to max_seq - prompt length (or raise "
                f"{self.ceiling_hint})")
        n_pages = self.pool.pages_for(n_tokens_worst_case)
        try:
            slot = self.slot_pages.index(None)
        except ValueError:
            return None
        shared: List[int] = []
        skip = 0
        cow: Optional[Tuple[int, int]] = None
        if self.prefix is not None and prompt is not None:
            shared, skip, cow = self.prefix.match(prompt)
        n_tail = n_pages - len(shared)
        if not self.allocator.can_alloc(n_tail):
            if self.prefix is not None:
                self.prefix.evict(n_tail)
            if not self.allocator.can_alloc(n_tail):
                return None
        tail = self.allocator.alloc(n_tail)
        for p in shared:
            self.allocator.incref(p)
        if cow is not None:
            # divergence inside block len(shared): adopt the shared
            # source just long enough to duplicate it into the first
            # tail page (one device-side page copy), then drop the
            # shared ref — the copy is privately ours and the tail
            # prefill overwrites it from the divergence point on.
            src, t = cow
            self.allocator.incref(src)
            self.pools = tuple(self._cow(
                *self.pools, jnp.asarray(src, jnp.int32),
                jnp.asarray(tail[0], jnp.int32)))
            self.allocator.decref(src)
            self.cow_copies += 1
            skip += t
        pages = shared + tail
        self.slot_pages[slot] = pages
        self.tables.assign(slot, pages)
        self.slot_skip[slot] = skip
        return slot

    def release(self, slot: int) -> None:
        """Eviction-on-finish: one reference dropped per page — unshared
        pages return to the free list immediately; pages the prefix
        index (or another block table) still holds stay resident. The
        block-table row resets to the scratch page."""
        pages = self.slot_pages[slot]
        if pages is not None:
            self.allocator.free(pages)
        self.slot_pages[slot] = None
        self.slot_skip[slot] = 0
        self.tables.clear(slot)

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def prefill_chunk(self, slot: int, prompt: np.ndarray,
                      start: int) -> Tuple[int, Optional[jax.Array]]:
        """Run ONE bucket-sized prefill chunk of ``prompt`` beginning at
        ``start``; returns (next_start, first_token) where first_token
        is the greedy argmax at the last prompt position — None while
        chunks remain, and on the last chunk a device scalar the host
        has NOT read: the call never waits for the device, ``int()`` of
        it does. The same token is already among the slots' newest on
        the device, so a decode step can follow at once. The scheduler
        calls this once per cycle so in-flight decodes stall one chunk
        at a time, never the whole prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size > self.max_seq:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds the serving "
                f"context ceiling {self.max_seq} "
                f"({self.ceiling_hint})")
        n_real = min(prompt.size - start,
                     self.bucket_for(prompt.size - start))
        bucket = self.bucket_for(n_real)
        self.prefill_pages["chunks"] += 1
        self.prefill_pages["walked"] += -(-(start + n_real) // self.page)
        self.prefill_pages["table"] += self.n_max_pages
        with trace.span(
                "engine.prefill.dispatch", cat=trace.CAT_SERVE,
                attrs=({"slot": slot, "start": start, "tokens": n_real,
                        "bucket": bucket} if trace.enabled() else None)):
            bt_row = jnp.asarray(self.tables.tables[slot].copy())
            chunk = np.zeros((bucket,), np.int32)
            chunk[:n_real] = prompt[start:start + n_real]
            tok, _ = self._step(
                self._prefill[bucket], bt_row, *self._slot_arg(slot),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(n_real, jnp.int32), jnp.asarray(chunk))
        start += n_real
        if start < prompt.size:
            return start, None
        self.tables.lengths[slot] = prompt.size
        if self.prefix is not None:
            # prompt fully resident: index every FULL prompt block so
            # the next matching prompt adopts these pages (the index
            # takes its own ref — the pages outlive this request)
            self.prefix.register(prompt, self.slot_pages[slot] or [])
        self._newest = self._put_first(self._newest, tok, np.int32(slot))
        return start, tok

    def prefill(self, slot: int, prompt: np.ndarray) -> int:
        """Run the whole prompt through prefill chunks back-to-back;
        returns the FIRST generated token. Direct-API convenience — the
        scheduler drives :meth:`prefill_chunk` incrementally instead."""
        start, token = 0, None
        while token is None:
            start, token = self.prefill_chunk(slot, prompt, start)
        with trace.span(
                "engine.prefill.wait", cat=trace.CAT_SERVE,
                attrs={"slot": slot} if trace.enabled() else None):
            return int(token)

    def decode_step(self, tokens: Optional[np.ndarray] = None,
                    active: Optional[np.ndarray] = None
                    ) -> Union[np.ndarray, jax.Array]:
        """One batched decode step. ``active`` masks the slots actually
        decoding — slots outside it (empty, or MID-PREFILL under the
        chunk interleave) are presented to the compiled step with a
        scratch block table and length 0, so their garbage write can
        never land in pages a concurrent prefill owns. Cached lengths of
        active slots advance by one when the step is enqueued; the step
        reads a snapshot of the tables taken then.

        What the caller hands in decides when it sees the result:

        - ``tokens`` from the host (``tokens[s]`` is slot s's input
          token, ignored for inactive slots): the step's next tokens,
          read back before the call returns.
        - ``None``: every active slot continues from the newest token a
          program of the engine produced for it (its prompt's first, or
          the last step's), which never left the device. The step is
          enqueued, THEN the step before it, if the host has not read
          that yet, is waited for, so the device holds a step while the
          host reads and schedules; the result is this step's next
          tokens as a device array nobody has read (``np.asarray`` of it
          waits; the next such call, or :meth:`drain`, reads it)."""
        if active is None:
            # length 0 means the slot is reserved but its prompt has not
            # finished prefilling (lengths is set at the FINAL chunk) —
            # exactly the slots the masking contract must protect, so
            # the default excludes them too, not just empty slots.
            active = (np.array([p is not None for p in self.slot_pages])
                      & (self.tables.lengths > 0))
        late = self._unread
        walked = -(-(self.tables.lengths[active] + 1) // self.page)
        self.decode_pages["steps"] += 1
        self.decode_pages["walked"] += int(
            np.minimum(walked, self.n_max_pages).sum())
        self.decode_pages["table"] += self.slots * self.n_max_pages
        with trace.span(
                "engine.decode.dispatch", cat=trace.CAT_SERVE,
                attrs=({"active": int(active.sum())} if trace.enabled()
                       else None)):
            bt, ln = self.tables.device_views(active)
            feed = (self._newest if tokens is None
                    else jnp.asarray(np.asarray(tokens, np.int32)))
            with (trace.span("engine.decode.ahead", cat=trace.CAT_SERVE)
                  if late is not None else contextlib.nullcontext()):
                nxt, _ = self._step(self._decode, bt, ln, feed)
            self.tables.lengths[active] += 1
        self.decode_counts["steps"] += 1
        self.decode_counts["dispatched_ahead"] += late is not None
        self._newest = self._unread = nxt
        if tokens is not None:
            return self.drain("direct")
        if late is not None:
            self._read(late)
        return nxt

    def drain(self, reason: str) -> Optional[np.ndarray]:
        """Read the decode step in flight with nothing queued behind it
        (None where the host has read every step): the device runs dry
        while the host goes on. ``reason`` says who had to, for
        ``stats()["decode"]["drained"]``."""
        late, self._unread = self._unread, None
        if late is None:
            return None
        self._count_drain(reason)
        return self._read(late)

    def _read(self, late: jax.Array) -> np.ndarray:
        """A decode step's tokens on the host (``engine.decode.wait``).
        While somebody reads the spans the two halves are told apart:
        ``.ready`` is the device not done yet, ``.copy`` the copy to the
        host and this thread's wake-up."""
        with trace.span("engine.decode.wait", cat=trace.CAT_SERVE):
            if trace.active():
                with trace.span("engine.decode.wait.ready",
                                cat=trace.CAT_SERVE):
                    late.block_until_ready()
                with trace.span("engine.decode.wait.copy",
                                cat=trace.CAT_SERVE):
                    return np.asarray(late)
            return np.asarray(late)

    def _count_drain(self, reason: str) -> None:
        drained = self.decode_counts["drained"]
        drained[reason] = drained.get(reason, 0) + 1

    # -- speculative decode (draft K, verify all K in one step) --------------
    def propose_drafts(self, tokens: np.ndarray,
                       active: np.ndarray) -> np.ndarray:
        """K draft tokens per slot from the truncated-layer draft model
        (``HOROVOD_SERVE_DRAFT=truncate:N``): K sequential decode-shaped
        steps through the target's first N layers. The draft writes its
        layers' K/V into the shared pool at the speculated positions —
        verify recomputes and overwrites the same values, so the pool
        never holds a draft-only value any reader can observe."""
        if self._draft is None:
            raise RuntimeError(
                "propose_drafts needs HOROVOD_SERVE_DRAFT=truncate:N "
                f"(engine built with {self.draft_spec!r})")
        k = self.spec_k
        drafts = np.zeros((self.slots, k), np.int32)
        bt_np = self.tables.tables.copy()
        ln_np = self.tables.lengths.copy()
        bt_np[~active] = self.pool.scratch_page
        ln_np[~active] = 0
        toks = np.asarray(tokens, np.int32).copy()
        toks[~active] = 0
        for i in range(k):
            with trace.span("engine.draft.dispatch", cat=trace.CAT_SERVE):
                nxt, _ = self._step(
                    self._draft, jnp.asarray(bt_np), jnp.asarray(ln_np),
                    jnp.asarray(toks))
            with trace.span("engine.draft.wait", cat=trace.CAT_SERVE):
                nxt = np.asarray(nxt)
            drafts[:, i] = nxt
            toks = np.where(active, nxt, 0).astype(np.int32)
            ln_np = ln_np + active.astype(np.int32)
        return drafts

    def spec_step(self, tokens: np.ndarray, drafts: np.ndarray,
                  active: Optional[np.ndarray] = None) -> np.ndarray:
        """One batched speculative VERIFY step: ``tokens[s]`` is slot
        s's last accepted token, ``drafts[s]`` its K proposed
        continuations. Runs the decode body once at batch
        ``slots*(K+1)`` — row (s, i) consumes draft i (row 0 the
        accepted token) at position ``len_s + i``, every row's K/V
        landing before the attention so causality over the drafts is
        exact. Returns ``out [slots, K+1]``: out[s, i] is bitwise the
        token sequential decode would emit after consuming rows 0..i.

        Lengths of active slots advance OPTIMISTICALLY by K+1; the
        scheduler computes each slot's accepted prefix and calls
        :meth:`rollback` with the rejected count."""
        if self._verify is None:
            raise RuntimeError(
                "spec_step needs HOROVOD_SERVE_DRAFT != 'off' "
                "(the verify executable is built at engine boot)")
        k = self.spec_k
        if active is None:
            active = (np.array([p is not None for p in self.slot_pages])
                      & (self.tables.lengths > 0))
        with trace.span(
                "engine.verify.dispatch", cat=trace.CAT_SERVE,
                attrs=({"active": int(active.sum()), "k": k}
                       if trace.enabled() else None)):
            rows = self.slots * (k + 1)
            bt = np.repeat(self.tables.tables, k + 1, axis=0)
            ln = (np.repeat(self.tables.lengths, k + 1)
                  + np.tile(np.arange(k + 1, dtype=np.int32), self.slots))
            toks = np.concatenate(
                [np.asarray(tokens, np.int32).reshape(-1, 1),
                 np.asarray(drafts, np.int32).reshape(self.slots, k)],
                axis=1).reshape(rows)
            row_active = np.repeat(active, k + 1)
            bt[~row_active] = self.pool.scratch_page
            ln[~row_active] = 0
            toks[~row_active] = 0
            nxt, _ = self._step(
                self._verify, jnp.asarray(bt),
                jnp.asarray(ln.astype(np.int32)), jnp.asarray(toks))
        self.tables.lengths[active] += k + 1
        self._count_drain("speculation")
        with trace.span("engine.verify.wait", cat=trace.CAT_SERVE):
            return np.asarray(nxt).reshape(self.slots, k + 1)

    def rollback(self, slot: int, n_rejected: int) -> None:
        """Accept-prefix rollback: drop the rejected speculative suffix
        of a slot — pure length bookkeeping. The suffix's page writes
        are dead (masked by the rolled-back length, overwritten by the
        next step's verify before anything attends over them), and the
        slot's reserved pages stay put: the worst-case reservation
        covers the request's future growth, so its COW/tail pages
        return through the normal retire decref, never mid-flight."""
        n = int(n_rejected)
        if self.slot_stateful:
            raise ValueError(
                f"rollback of slot {slot}: {type(self.cfg).__name__} keeps "
                f"recurrent state per slot, and a state that has taken a "
                f"token in cannot give it back")
        if not (0 <= n <= int(self.tables.lengths[slot])):
            raise ValueError(
                f"rollback of {n} tokens on slot {slot} with length "
                f"{int(self.tables.lengths[slot])}")
        self.tables.lengths[slot] -= n

    def _pool_held(self) -> Dict[str, Any]:
        """The pool as its rows describe it against the pool as it lies
        in memory: each array's row, the arrays' bytes (``bytes``, every
        device's), and what the decode program's arguments hold on one
        device once the weights, the model's state and the step's
        integers are taken off (``resident_bytes``, from the compiled
        program's ``memory_analysis()``). ``padding`` is that over the
        same device's share of ``bytes``: 1.0 where a row fills the
        lanes, 2.0 where a head of 64 stood alone on 128 of them."""
        def on_device(arrays) -> int:
            return sum(
                int(np.prod(a.sharding.shard_shape(a.shape)))
                * a.dtype.itemsize for a in jax.tree.leaves(arrays))

        out: Dict[str, Any] = {
            "rows": {r.name: list(r.row) for r in self.pool.rows},
            "bytes": self.pool.nbytes(),
            "resident_bytes": None, "padding": None}
        arguments = self.program_argument_bytes.get("serve_decode")
        if arguments is not None:
            integers = 4 * self.slots * (self.n_max_pages + 2)
            out["resident_bytes"] = (
                arguments - on_device(self.params) - on_device(self.state)
                - integers)
            out["padding"] = round(
                out["resident_bytes"] / float(on_device(self.pools)), 4)
        return out

    def occupancy(self) -> float:
        used = sum(1 for p in self.slot_pages if p is not None)
        return used / float(self.slots)

    def stats(self) -> Dict[str, Any]:
        free = self.allocator.free_pages
        own = ({} if self.model.stats is None
               else self.model.stats(self.cfg, self.state))
        return {
            **own,
            "slots": self.slots,
            "occupied": sum(1 for p in self.slot_pages if p is not None),
            "page": self.page,
            "pages_total": self.pool.n_pages,
            "pages_free": free,
            "pages_shared": self.allocator.shared_pages,
            "pool": {
                "free": free,
                "shared": self.allocator.shared_pages,
                "utilization": round(
                    1.0 - free / float(self.pool.n_pages), 4),
                **self.pool_held,
            },
            "kv_pool_bytes": self.pool.nbytes(),
            "weights": dict(self.weights),
            "prefill_buckets": list(self.buckets),
            "prefix_cache": self.prefix_cache,
            "prefix_index": (self.prefix.stats()
                             if self.prefix is not None else None),
            "cow_copies": self.cow_copies,
            "decode": {**self.decode_counts,
                       "drained": dict(self.decode_counts["drained"])},
            "prefill_pages": dict(self.prefill_pages),
            "decode_pages": dict(self.decode_pages),
            "draft": self.draft_spec,
            "spec_k": self.spec_k,
            "builds": self.builds,
            "store_outcomes": dict(self.store_outcomes),
            "program_temp_bytes": dict(self.program_temp_bytes),
            "program_argument_bytes": dict(self.program_argument_bytes),
            # executables that rejected their inputs and now dispatch
            # through the jit fall-back (wrap_compiled); empty is healthy
            "store_rejected": sorted(
                label for label, fn in self._dispatch.items()
                if getattr(fn, "hvd_store_rejected", None)),
            "tp": self._tp_size,
        }


# ---------------------------------------------------------------------------
# train -> serve handoff
# ---------------------------------------------------------------------------

def load_for_serving(ckpt_dir: str, mesh: Optional[Mesh],
                     cfg: tfm.TransformerConfig,
                     template: Optional[Any] = None
                     ) -> Tuple[int, Any]:
    """(step, params) from the newest committed training snapshot in
    ``ckpt_dir``, placed onto the SERVING mesh per ``param_specs(cfg)``.

    The snapshot is the full TrainState — optimizer leaves (momentum,
    WireState error-feedback residual, step counter) restore alongside
    the params and are then dropped; only the param tree is placed.
    A world-mismatched snapshot goes through the documented reshard
    path: orbax format restores through ``template=`` (pass the saved
    TrainState's abstract tree), anything else raises the checkpoint
    subsystem's descriptive ``CheckpointMismatchError`` naming the fix.
    """
    from horovod_tpu.resilience import async_checkpoint as ac
    got = ac.restore_latest(ckpt_dir, template=template)
    if got is None:
        raise FileNotFoundError(
            f"train->serve handoff: no committed checkpoint under "
            f"{ckpt_dir} (is HOROVOD_CKPT_DIR right, and did the "
            f"training run commit at least one snapshot?)")
    step, state = got
    params = getattr(state, "params", None)
    if params is None and isinstance(state, dict):
        params = state.get("params")
    if params is None:
        params = state          # params-only tree saved directly
    # Validation goes through the HVD8xx compat tier's diff engine
    # (analysis/rules_compat): the runtime error here and the static
    # `hvd.compat_report` finding describe one defect in one voice —
    # and `hvdlint --compat` can prove this gate green BEFORE a replica
    # commits to the swap.
    from horovod_tpu.analysis import rules_compat
    expected = jax.eval_shape(lambda: tfm.init_params(
        cfg, jax.random.PRNGKey(0)))
    got_td = jax.tree.structure(params)
    if got_td != jax.tree.structure(expected):
        raise ValueError(rules_compat.structure_message(
            str(got_td), str(jax.tree.structure(expected))))
    # Structure alone cannot tell models apart — layer stacks are
    # stacked arrays, so a 4-layer or wider snapshot has the identical
    # tree. Leaf shapes are the model geometry; name the first mismatch
    # instead of dying deep inside the engine's scan trace.
    def _shapes(tree):
        return {jax.tree_util.keystr(kp): (tuple(leaf.shape), "")
                for kp, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}
    diff = rules_compat.tree_diff(_shapes(params), _shapes(expected))
    if diff["shape"]:
        name, got_shape, want_shape = diff["shape"][0]
        raise ValueError(rules_compat.geometry_message(
            name, got_shape, want_shape))
    if cfg.tp_axis and mesh is not None:
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), tfm.param_specs(cfg),
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shardings)
    else:
        params = jax.tree.map(jnp.asarray, params)
    logger.info("train->serve handoff: restored step %d from %s "
                "(optimizer/residual leaves dropped)", step, ckpt_dir)
    return int(step), params


# ---------------------------------------------------------------------------
# module-level registry (the /healthz `serving` block reads this)
# ---------------------------------------------------------------------------

_active_engine: Optional[ServeEngine] = None


def _register_engine(engine: ServeEngine) -> None:
    global _active_engine
    _active_engine = engine


def active_engine() -> Optional[ServeEngine]:
    return _active_engine


def reset_for_tests() -> None:
    global _active_engine
    _active_engine = None
