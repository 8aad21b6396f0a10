"""Disk-backed AOT executable store (ROADMAP item 5, docs/artifact_store.md).

The reference's response cache (response_cache.h:45) exists so steady
state never renegotiates what a fingerprint already proves; this module
extends the same principle across PROCESS boundaries: a compiled XLA
executable, once paid for, is serialized (``jax.experimental.
serialize_executable``) under a composite fingerprint and every later
process — a preemption auto-resume, a ``HOROVOD_VERIFY_STEP`` run, a
serving replica, the next ``bucket=auto`` sweep — loads it instead of
recompiling.

Key = sha256 over the canonical JSON of::

    {kind,                    # step | serve | eager_fused | blob kinds
     env fingerprint,         # jax/jaxlib versions, backend platform +
                              # version, device kind/count, process count
     components}              # per-consumer: program signature, mesh
                              # fingerprint (resilience manifest shape),
                              # autotune.grad_signature, resolved
                              # program-keying knobs (wire tier, bucket
                              # bytes, DCN schedule, ...)

A flipped knob, a changed mesh, or a different gradient payload each
produce a different digest — a stale executable can never load. The
HVD503 collective-order fingerprint rides in the entry header: when the
in-process order registry (analysis/ir.py) already holds a fingerprint
for the same step tag and the stored one disagrees, the entry is treated
as stale and missed.

Publish discipline is PR 3's atomic-commit protocol: the full entry is
written to a ``.tmp-``-prefixed sibling, one ``schedhooks.rename``
publishes it; readers validate MAGIC + format version + env fingerprint
+ payload sha256 before deserializing, so partial, corrupt, truncated or
version-skewed artifacts log and fall back to recompile — never crash.
Store I/O runs under ``retry_fs`` on the optional fault-domain site
``artifact_store``: an exhausted budget sheds the store (compile as
usual) instead of failing the run.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from horovod_tpu.config import knobs
from horovod_tpu.utils import schedhooks
from horovod_tpu.utils.logging import get_logger

logger = get_logger("horovod_tpu.store")

MAGIC = b"HVDSTORE\x01"
FORMAT_VERSION = 1
_SUFFIX = ".hvdx"
_TMP_PREFIX = ".tmp-"
SITE = "artifact_store"

# Knobs that key the compiled program (resolved values): flipping any of
# these changes what the trace produces, so they are part of every entry's
# composite fingerprint. Deliberately NOT the whole registry — a changed
# metrics port must not invalidate a multi-minute compile.
PROGRAM_KNOBS = (
    "HOROVOD_GRADIENT_COMPRESSION",
    "HOROVOD_GRADIENT_ERROR_FEEDBACK",
    "HOROVOD_GRADIENT_BUCKET_BYTES",
    "HOROVOD_DCN_SCHEDULE",
    "HOROVOD_DCN_MESH",
    "HOROVOD_DCN_VIRTUAL_SLICES",
    "HOROVOD_FUSION_THRESHOLD",
    "HOROVOD_BATCH_D2D_MEMCOPIES",
    "HOROVOD_HIERARCHICAL_ALLREDUCE",
    "HOROVOD_HIERARCHICAL_ALLGATHER",
    "HOROVOD_TORUS_ALLREDUCE",
    "HOROVOD_TPU_DONATE_BUFFERS",
    "HOROVOD_TPU_MATMUL_PRECISION",
    "HOROVOD_CE_BLOCK_VOCAB",
    "HOROVOD_NUMERICS",
)


def env_fingerprint() -> Dict[str, Any]:
    """Toolchain + backend identity an executable is only valid under.
    Serialized PJRT executables are not portable across compiler
    versions or device kinds, so ANY difference here is a miss (logged
    as version skew, not corruption). The framework's own version is
    part of it: eager fused programs are built by repo code from their
    signature, so a release that changes the builders must invalidate
    (step-tier entries additionally key on the lowered program text —
    :func:`program_text_hash`)."""
    fp: Dict[str, Any] = {"format": FORMAT_VERSION}
    try:
        from horovod_tpu.version import __version__ as _hvd_version
        fp["horovod_tpu"] = _hvd_version
    except Exception:
        pass
    try:
        import jax
        import jaxlib
        fp["jax"] = jax.__version__
        fp["jaxlib"] = getattr(jaxlib, "__version__", "")
        dev = jax.devices()[0]
        fp["platform"] = getattr(dev, "platform", "")
        fp["platform_version"] = getattr(
            dev.client, "platform_version", "")
        fp["device_kind"] = getattr(dev, "device_kind", "")
        fp["n_devices"] = jax.device_count()
        fp["process_count"] = jax.process_count()
    except Exception:
        logger.debug("env fingerprint incomplete", exc_info=True)
    return fp


def mesh_fingerprint() -> Dict[str, Any]:
    """The checkpoint manifest's topology identity (resilience/
    async_checkpoint.mesh_fingerprint) — the same fields that gate a
    snapshot restore gate an executable load."""
    from horovod_tpu.resilience.async_checkpoint import (
        mesh_fingerprint as _mfp,
    )
    return _mfp()


def program_knob_fingerprint() -> Dict[str, str]:
    """Resolved values of the program-keying knobs (stringified so the
    dict is canonically JSON-able)."""
    out = {}
    for name in PROGRAM_KNOBS:
        try:
            out[name] = str(knobs.get(name))
        except KeyError:
            continue
    return out


class StoreKey:
    """One composite fingerprint: ``kind`` + env fingerprint + the
    consumer's components, canonicalized to JSON; ``digest`` names the
    entry file."""

    def __init__(self, kind: str, components: Dict[str, Any],
                 env: Optional[Dict[str, Any]] = None):
        self.kind = str(kind)
        self.env = env if env is not None else env_fingerprint()
        self.components = components
        self.canonical = json.dumps(
            {"kind": self.kind, "env": self.env,
             "components": components},
            sort_keys=True, default=str)
        self.digest = hashlib.sha256(
            self.canonical.encode()).hexdigest()[:32]

    def __repr__(self) -> str:
        return f"StoreKey({self.kind}, {self.digest})"


# ---------------------------------------------------------------------------
# metrics (lazy — the store must stay importable before the plane is up)
# ---------------------------------------------------------------------------

def _m_counter(name: str, help_: str):
    from horovod_tpu import metrics as M
    return M.counter(name, help_)


def _count(name: str, help_: str, n: float = 1.0) -> None:
    try:
        _m_counter(name, help_).inc(n)
    except Exception:
        pass


def _set_size_gauge(nbytes: int) -> None:
    try:
        from horovod_tpu import metrics as M
        M.gauge("hvd_artifact_store_size_bytes",
                "Bytes currently held by the persistent compiled-"
                "artifact store (post-eviction)",
                aggregation="leader").set(float(nbytes))
    except Exception:
        pass


class ArtifactStore:
    """One store root directory. Entries are single files
    ``<digest>.hvdx``: MAGIC + u32 header length + JSON header +
    payload; the header alone is enough to decide loadability (env
    fingerprint, payload sha256, order fingerprint), the payload is the
    pickled ``(serialized, in_tree, out_tree)`` triple of
    ``serialize_executable.serialize`` — or a JSON blob for meta-only
    entries (bucket-auto sweep evidence)."""

    def __init__(self, root: str, max_bytes: int = 0):
        self.root = os.path.abspath(os.path.expanduser(root))
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        # per-instance tallies (module counters aggregate across
        # instances; these back stats()/healthz/ledger)
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "publishes": 0, "bytes_written": 0,
                       "compile_seconds_saved": 0.0, "errors": 0,
                       "shed": 0}

    # -- paths ---------------------------------------------------------------
    def _path(self, key: StoreKey) -> str:
        return os.path.join(self.root, key.digest + _SUFFIX)

    def _ensure_root(self) -> None:
        os.makedirs(self.root, exist_ok=True)

    # -- tallies -------------------------------------------------------------
    def _tally(self, field: str, n: float = 1) -> None:
        with self._lock:
            self._stats[field] += n

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._stats)
        out["compile_seconds_saved"] = round(
            out["compile_seconds_saved"], 6)
        out["root"] = self.root
        out["max_bytes"] = self.max_bytes
        try:
            # one directory scan: stats() serves every /healthz probe
            entries = self._entries()
            out["size_bytes"] = sum(nb for _, nb, _ in entries)
            out["entries"] = len(entries)
        except OSError:
            out["size_bytes"] = None
            out["entries"] = None
        return out

    def _miss(self, reason: str, path: str, detail: str = "") -> None:
        self._tally("misses")
        _count("hvd_artifact_store_misses_total",
               "Artifact-store lookups that fell back to a compile")
        if reason not in ("absent",):
            # corrupt/skewed/stale entries are worth a line; a plain
            # absent key is the normal cold path
            logger.warning("artifact store: %s entry ignored (%s)%s — "
                           "falling back to recompile", reason, path,
                           f": {detail}" if detail else "")

    # -- read ----------------------------------------------------------------
    def _read_entry(self, key: StoreKey) -> Optional[Tuple[dict, bytes]]:
        """(header, payload) of a validated entry, or None (counted +
        logged as a miss). Never raises."""
        from horovod_tpu.resilience import chaos, faults
        path = self._path(key)
        if faults.should_shed(SITE):
            self._tally("shed")
            self._miss("absent", path)
            return None
        try:
            def _read() -> Optional[bytes]:
                chaos.on_fs("store_read", path)
                if not os.path.exists(path):
                    return None
                with open(path, "rb") as f:
                    return f.read()
            raw = faults.retry_fs(SITE, _read)
        except faults.RetryBudgetExhausted as e:
            self._tally("errors")
            self._miss("unreadable", path, str(e))
            return None
        except OSError as e:
            self._tally("errors")
            self._miss("unreadable", path, str(e))
            return None
        if raw is None:
            self._miss("absent", path)
            return None
        if chaos.on_store_load(path):
            self._miss("corrupt", path, "chaos store_corrupt")
            return None
        if len(raw) < len(MAGIC) + 4 or not raw.startswith(MAGIC):
            self._miss("corrupt", path, "bad magic/truncated")
            return None
        (hlen,) = struct.unpack(">I", raw[len(MAGIC):len(MAGIC) + 4])
        body = raw[len(MAGIC) + 4:]
        if len(body) < hlen:
            self._miss("corrupt", path, "truncated header")
            return None
        try:
            header = json.loads(body[:hlen].decode("utf-8"))
        except (UnicodeDecodeError, ValueError):
            self._miss("corrupt", path, "unparseable header")
            return None
        payload = body[hlen:]
        if header.get("env") != key.env:
            self._miss("version-skewed", path,
                       f"stored under {header.get('env')}, "
                       f"current {key.env}")
            return None
        if header.get("components") != json.loads(
                json.dumps(key.components, sort_keys=True, default=str)):
            self._miss("mismatched", path, "component collision")
            return None
        digest = hashlib.sha256(payload).hexdigest()
        if digest != header.get("payload_sha256"):
            self._miss("corrupt", path, "payload digest mismatch")
            return None
        return header, payload

    def _hit(self, key: StoreKey, header: dict) -> None:
        self._tally("hits")
        saved = float(header.get("compile_seconds") or 0.0)
        self._tally("compile_seconds_saved", saved)
        _count("hvd_artifact_store_hits_total",
               "Artifact-store lookups served from disk (compile "
               "skipped)")
        if saved > 0:
            _count("hvd_compile_seconds_saved_total",
                   "Compile seconds skipped by artifact-store hits "
                   "(the publish-time measured cost of each entry)",
                   saved)
        try:
            os.utime(self._path(key))      # LRU victim order is mtime
        except OSError:
            pass

    def load_executable(self, key: StoreKey,
                        order_tag: Optional[str] = None) -> Optional[Any]:
        """The deserialized ``jax.stages.Compiled`` for ``key``, or
        None (miss — absent, corrupt, truncated, version-skewed, shed,
        or collective-order-stale; all logged, none raised)."""
        entry = self._read_entry(key)
        if entry is None:
            return None
        header, payload = entry
        path = self._path(key)
        if order_tag and header.get("order_fingerprint"):
            # HVD503 continuity: when this process already verified a
            # program under the same tag, the stored schedule identity
            # must agree — a silent schedule change is exactly what the
            # order registry exists to catch.
            try:
                from horovod_tpu.analysis.ir import order_fingerprints
                live = order_fingerprints().get(order_tag)
            except Exception:
                live = None
            if live is not None and live != header["order_fingerprint"]:
                self._miss("order-stale", path,
                           f"stored order {header['order_fingerprint']} "
                           f"!= verified {live} for tag {order_tag}")
                return None
        try:
            import jax
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in header["device_ids"]]
            if not reloadable(devices[0].platform, len(devices),
                              len(by_id)):
                self._miss("unloadable", path,
                           f"a {len(devices)}-of-{len(by_id)}-chip program "
                           f"cannot be reloaded on this TPU runtime")
                return None
            compiled = _deserialize_onto(devices, *pickle.loads(payload))
        except Exception as e:
            self._tally("errors")
            self._miss("corrupt", path,
                       f"deserialize failed ({type(e).__name__}: {e})")
            return None
        self._hit(key, header)
        return compiled

    def load_blob(self, key: StoreKey) -> Optional[Any]:
        """Meta-only entry (JSON payload) for ``key``, or None."""
        entry = self._read_entry(key)
        if entry is None:
            return None
        header, payload = entry
        try:
            obj = json.loads(payload.decode("utf-8"))
        except (UnicodeDecodeError, ValueError) as e:
            self._miss("corrupt", self._path(key), str(e))
            return None
        self._hit(key, header)
        return obj

    def contains(self, key: StoreKey) -> bool:
        return os.path.exists(self._path(key))

    # -- write ---------------------------------------------------------------
    def _publish(self, key: StoreKey, payload: bytes,
                 meta: Dict[str, Any]) -> bool:
        from horovod_tpu.resilience import chaos, faults
        if faults.should_shed(SITE):
            self._tally("shed")
            return False
        header = dict(meta)
        header["env"] = key.env
        header["kind"] = key.kind
        header["components"] = json.loads(
            json.dumps(key.components, sort_keys=True, default=str))
        header["payload_sha256"] = hashlib.sha256(payload).hexdigest()
        header["payload_bytes"] = len(payload)
        header["created_unix"] = time.time()
        hdr = json.dumps(header, sort_keys=True).encode("utf-8")
        blob = MAGIC + struct.pack(">I", len(hdr)) + hdr + payload
        final = self._path(key)
        tmp = os.path.join(
            self.root,
            f"{_TMP_PREFIX}{key.digest}-{os.getpid()}-"
            f"{os.urandom(4).hex()}")
        try:
            def _write() -> None:
                self._ensure_root()
                chaos.on_fs("store_write", tmp)
                with open(tmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                chaos.on_fs("store_rename", final)
                # ONE rename publishes; readers can never observe a
                # partial entry. Routed through the schedhooks seam so
                # the crash-at-publish interleavings are explorable.
                schedhooks.rename(tmp, final)
            faults.retry_fs(SITE, _write)
        except (faults.RetryBudgetExhausted, OSError,
                chaos.ChaosDenied) as e:
            self._tally("errors")
            logger.warning("artifact store: publish of %s failed (%s) — "
                           "entry skipped, training unaffected",
                           key, e)
            try:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            except OSError:
                pass
            return False
        self._tally("publishes")
        self._tally("bytes_written", len(blob))
        _count("hvd_artifact_store_bytes_total",
               "Bytes written to the persistent compiled-artifact "
               "store", len(blob))
        self._evict_to_budget()
        return True

    def publish_executable(self, key: StoreKey, compiled: Any, *,
                           compile_seconds: float = 0.0,
                           order_tag: Optional[str] = None,
                           extra_meta: Optional[Dict[str, Any]] = None
                           ) -> bool:
        """Serialize + atomically publish a compiled executable. False
        (logged) when the executable does not support serialization, the
        site is shed, or I/O fails — the caller keeps its in-memory
        executable either way."""
        try:
            from jax.experimental import serialize_executable as se
            serialized, in_tree, out_tree = se.serialize(compiled)
            payload = pickle.dumps((serialized, in_tree, out_tree))
        except Exception as e:
            logger.info("artifact store: %s not serializable (%s: %s) — "
                        "not persisted", key, type(e).__name__, e)
            return False
        meta: Dict[str, Any] = {"compile_seconds":
                                round(float(compile_seconds), 6),
                                "device_ids": device_ids(compiled)}
        if extra_meta:
            meta.update(extra_meta)
        if order_tag:
            meta["order_tag"] = order_tag
            fp = self._order_fingerprint(compiled, order_tag)
            if fp:
                meta["order_fingerprint"] = fp
        return self._publish(key, payload, meta)

    def publish_blob(self, key: StoreKey, obj: Any, *,
                     extra_meta: Optional[Dict[str, Any]] = None) -> bool:
        payload = json.dumps(obj, sort_keys=True, default=str).encode()
        return self._publish(key, payload, dict(extra_meta or {}))

    @staticmethod
    def _order_fingerprint(compiled: Any, tag: str) -> Optional[str]:
        """HVD503 schedule identity of the published program (best
        effort: optimized-HLO text parse)."""
        try:
            from horovod_tpu.analysis.rules_ir import (
                collective_fingerprint, hlo_collectives,
            )
            return collective_fingerprint(
                hlo_collectives(compiled.as_text()))
        except Exception:
            logger.debug("order fingerprint for %s unavailable", tag,
                         exc_info=True)
            return None

    # -- eviction ------------------------------------------------------------
    def _entries(self) -> List[Tuple[str, int, float]]:
        """[(path, nbytes, mtime)] of committed entries. ``.tmp-``
        leftovers from a crashed publish are invisible to readers and
        reaped here once stale."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        now = time.time()
        for name in sorted(names):
            path = os.path.join(self.root, name)
            if name.startswith(_TMP_PREFIX):
                try:
                    if now - os.path.getmtime(path) > 3600:
                        os.unlink(path)       # crashed publish, stale
                except OSError:
                    pass
                continue
            if not name.endswith(_SUFFIX):
                continue
            try:
                st = os.stat(path)
                out.append((path, int(st.st_size), st.st_mtime))
            except OSError:
                continue
        return out

    def total_bytes(self) -> int:
        return sum(nb for _, nb, _ in self._entries())

    def _evict_to_budget(self) -> None:
        """Size-budgeted LRU: oldest-mtime entries go first until the
        store fits HOROVOD_ARTIFACT_STORE_MAX_BYTES (0 = unlimited).
        Hits re-touch mtime, so hot entries survive."""
        if self.max_bytes <= 0:
            _set_size_gauge(self.total_bytes())
            return
        entries = sorted(self._entries(), key=lambda e: e[2])
        total = sum(nb for _, nb, _ in entries)
        for path, nb, _ in entries:
            if total <= self.max_bytes:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= nb
            self._tally("evictions")
            _count("hvd_artifact_store_evictions_total",
                   "Artifact-store entries evicted by the size-budgeted "
                   "LRU (HOROVOD_ARTIFACT_STORE_MAX_BYTES)")
            logger.info("artifact store: evicted %s (%d bytes) to fit "
                        "the %d-byte budget", os.path.basename(path),
                        nb, self.max_bytes)
        _set_size_gauge(total)

    # -- keys ----------------------------------------------------------------
    def key(self, kind: str, **components: Any) -> StoreKey:
        return StoreKey(kind, components)


def read_entry_headers(root: str) -> List[Dict[str, Any]]:
    """Parsed headers of every ``.hvdx`` entry under ``root`` — the
    compat tier's (HVD803) view of the store. Each dict is the entry's
    JSON header plus ``file`` (basename) and ``payload_ok`` (the stored
    payload re-hashes to ``payload_sha256``). Unparseable or truncated
    entries are skipped, exactly like ``_read_entry`` would skip them;
    never raises on a per-entry basis (OSError from an unreadable root
    propagates — the caller reports the store as unscannable)."""
    out: List[Dict[str, Any]] = []
    root = os.path.abspath(os.path.expanduser(root))
    for name in sorted(os.listdir(root)):
        if not name.endswith(_SUFFIX) or name.startswith(_TMP_PREFIX):
            continue
        try:
            with open(os.path.join(root, name), "rb") as f:
                raw = f.read()
        except OSError:
            continue
        if len(raw) < len(MAGIC) + 4 or not raw.startswith(MAGIC):
            continue
        (hlen,) = struct.unpack(">I", raw[len(MAGIC):len(MAGIC) + 4])
        body = raw[len(MAGIC) + 4:]
        if len(body) < hlen:
            continue
        try:
            header = json.loads(body[:hlen].decode("utf-8"))
        except (UnicodeDecodeError, ValueError):
            continue
        payload = body[hlen:]
        header["file"] = name
        header["payload_ok"] = (
            hashlib.sha256(payload).hexdigest()
            == header.get("payload_sha256"))
        out.append(header)
    return out


# ---------------------------------------------------------------------------
# process-global store (HOROVOD_ARTIFACT_STORE)
# ---------------------------------------------------------------------------

_store: Optional[ArtifactStore] = None
_store_cfg: Optional[Tuple[str, int]] = None
_store_lock = threading.Lock()


def enabled() -> bool:
    return bool(str(knobs.get("HOROVOD_ARTIFACT_STORE") or "").strip())


def from_env() -> Optional[ArtifactStore]:
    """The configured store, or None when HOROVOD_ARTIFACT_STORE is
    empty. One instance per (root, budget) configuration — tallies
    accumulate across consumers, which is what /healthz reports."""
    global _store, _store_cfg
    root = str(knobs.get("HOROVOD_ARTIFACT_STORE") or "").strip()
    if not root:
        return None
    max_bytes = int(knobs.get("HOROVOD_ARTIFACT_STORE_MAX_BYTES"))
    cfg = (root, max_bytes)
    with _store_lock:
        if _store is None or _store_cfg != cfg:
            _store = ArtifactStore(root, max_bytes=max_bytes)
            _store_cfg = cfg
        return _store


def store_stats() -> Optional[Dict[str, Any]]:
    """Live tallies of the configured store (None when disabled) — the
    /healthz ``artifact_store`` block, the goodput-ledger record, and
    bench ``runtime_metrics`` all read this."""
    store = from_env()
    return store.stats() if store is not None else None


def reset_for_tests() -> None:
    global _store, _store_cfg
    with _store_lock:
        _store = None
        _store_cfg = None


# ---------------------------------------------------------------------------
# step-level consumers: key material + AOT adopt helpers
# ---------------------------------------------------------------------------

def device_ids(stage: Any) -> List[int]:
    """Ids of the devices a ``jax.stages.Lowered`` or ``Compiled`` is
    assigned to, in assignment order — key material (two placements of
    one program are two executables) and what a load must hand back to
    ``deserialize_and_load``. jax 0.9 exposes the assignment only on the
    stage's internals."""
    lowering = getattr(stage, "_lowering", None)
    if lowering is not None:
        return [int(d.id) for d in lowering._device_list]
    return [int(d.id) for d in
            stage._executable._unloaded_executable.device_list]


def reloadable(platform: str, n_devices: int, n_backend: int) -> bool:
    """Whether a serialized program over ``n_devices`` of the backend's
    ``n_backend`` may be loaded back. Measured on a 4-chip v5e host
    (PERF.md, PR 21): one chip or all four reload and run; a program
    spanning SOME of the chips halts the core at its first collective,
    with or without re-targeting — so on TPU that one recompiles."""
    return platform != "tpu" or n_devices in (1, n_backend)


def reload_keeps_layout(fmt: Any, shape: Tuple[int, ...], dtype: Any) -> bool:
    """Whether an executable that went through serialization (this
    store, JAX's persistent compile cache) still returns a result in
    the layout pinned on it. Measured, not assumed: libtpu 0.0.34
    honours a reloaded program's pinned ARGUMENT layouts but hands its
    results back in the compiler's default layout, while reporting the
    pinned one (PERF.md, PR 26) — a reloaded serve program would re-lay
    the whole KV pool on its way out and the next call would refuse it.
    The probe pins the layout of ``fmt`` (a ``jax.experimental.layout.
    Format``) on the identity over ``shape`` on one device of it,
    reloads that and looks at what comes out. True on the CPU backend,
    and wherever the default layout of ``shape`` is the pinned one."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as se
    from jax.experimental.layout import Format
    device = min(fmt.sharding.device_set, key=lambda d: d.id)
    one = jax.sharding.SingleDeviceSharding(device)
    compiled = jax.jit(
        lambda a: a * 1, out_shardings=Format(fmt.layout, one)).lower(
            jax.ShapeDtypeStruct(shape, dtype, sharding=one)).compile()
    reloaded = _deserialize_onto([device], *se.serialize(compiled))
    out = reloaded(jax.device_put(jnp.ones(shape, dtype), one))
    return (out.format.layout.major_to_minor
            == fmt.layout.major_to_minor)


def _deserialize_onto(devices: List[Any], serialized: bytes, in_tree: Any,
                      out_tree: Any) -> Any:
    """``serialize_executable.deserialize_and_load`` onto ``devices`` (the
    assignment the program was compiled for, in order).

    The library call cannot do it: its default is EVERY device of the
    backend — a one-chip program on a four-chip host then demands four
    shards — and even with ``execution_devices`` the TPU runtime assigns a
    reloaded program to the FIRST n devices unless the load carries
    compile options naming the assignment (measured, PERF.md PR 21; JAX's
    own compilation cache passes them for the same reason). So this is
    that function's body with the options added."""
    import io

    import jax
    import numpy as np
    from jax._src import compiler, dispatch
    from jax.experimental import serialize_executable as se

    backend = devices[0].client
    options = compiler.get_compile_options(
        num_replicas=1, num_partitions=len(devices),
        device_assignment=np.array(devices, dtype=object).reshape(1, -1),
        use_spmd_partitioning=True, backend=backend)
    options.parameter_is_tupled_arguments = dispatch.should_tuple_args(
        in_tree.num_leaves, backend.platform)

    class Unpickler(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "exec":
                return self.backend.deserialize_executable(
                    pid[1], executable_devices=self.execution_devices,
                    compile_options=options)
            return super().persistent_load(pid)

    unloaded, args_info_flat, no_kwargs = Unpickler(
        io.BytesIO(serialized), backend, devices).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info_flat), out_tree,
        no_kwargs=no_kwargs)


def aot_compile(jitted: Any, args: Tuple[Any, ...]) -> Tuple[Any, float]:
    """(compiled, seconds): explicit AOT lower+compile of a jitted
    callable with the run's concrete (or abstract) args."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def program_text_hash(lowered: Any) -> Optional[str]:
    """Content hash of a Lowered program's StableHLO text — the
    program-identity component of step-tier keys: an edit to the step
    or loss CODE (same shapes, same knobs) must change the key, or a
    stale executable could load. None when the text is unavailable
    (the caller's key then omits the component and stays conservative
    only through the other fingerprints)."""
    try:
        return hashlib.sha256(
            lowered.as_text().encode("utf-8", "replace")).hexdigest()[:16]
    except Exception:
        logger.debug("program text hash unavailable", exc_info=True)
        return None


def wrap_compiled(compiled: Any, fallback: Callable,
                  label: str = "step") -> Callable:
    """Dispatch through a (possibly store-loaded) AOT executable with a
    permanent fall-back to the original jitted callable on signature
    rejection (shapes/shardings moved away from the compiled ones —
    raised BEFORE execution/donation, so the retry is safe). Genuine
    runtime failures propagate unmasked. ``dispatch.hvd_store_rejected``
    is non-empty once the fall-back was taken."""
    rejected: List[bool] = []

    def dispatch(*a):
        if rejected:
            return fallback(*a)
        try:
            return compiled(*a)
        except (TypeError, ValueError) as e:
            logger.warning(
                "artifact store: cached %s executable rejected the "
                "inputs (%s: %s); falling back to the jit dispatch "
                "path", label, type(e).__name__, e)
            rejected.append(True)
            return fallback(*a)

    dispatch.hvd_store_compiled = compiled      # tests / introspection
    dispatch.hvd_store_rejected = rejected
    return dispatch


def step_key_components(step_fn: Any, args: Tuple[Any, ...], *,
                        lowered: Any = None) -> Dict[str, Any]:
    """Composite key material for a train/verify step executable: the
    step's symbol + input signature, the LOWERED program's content hash
    (``lowered`` — a code-only edit to the step/loss must miss; callers
    on the adopt/verify paths always have one in hand) and its device
    assignment (the same program placed on another chip is another
    executable), the mesh fingerprint, the resolved program-keying knobs, and — when the
    state arg carries params — the gradient payload signature with the
    bucket size 'auto' actually resolves to for it (autotune sweep
    cache)."""
    from horovod_tpu.analysis.ir import _anchor, _args_signature
    path, line, symbol = _anchor(step_fn)
    argsig = _args_signature(tuple(args))
    # NOTE: the HVD503 order tag is deliberately NOT key material — the
    # program hash already identifies the executable exactly (donation
    # included: buffer_donor attributes are in the lowered text), so a
    # verify run under a custom tag and a train-loop adoption of the
    # SAME program must share one entry (one compile total).
    comps: Dict[str, Any] = {
        "step": f"{symbol}@{argsig}",
        "mesh": mesh_fingerprint(),
        "knobs": program_knob_fingerprint(),
    }
    if lowered is not None:
        comps["program"] = program_text_hash(lowered)
        comps["devices"] = device_ids(lowered)
    params = getattr(args[0], "params", None) if args else None
    if params is not None:
        try:
            import jax
            from horovod_tpu import autotune
            leaves = [x for x in jax.tree_util.tree_leaves(params)
                      if hasattr(x, "shape")]
            world = jax.device_count()
            gsig = autotune.grad_signature(leaves, world)
            comps["grad_signature"] = gsig
            raw = knobs.get("HOROVOD_GRADIENT_BUCKET_BYTES")
            if raw == "auto":
                cached = autotune.bucket_cache_load().get(gsig)
                comps["resolved_bucket_bytes"] = int(
                    cached if cached is not None
                    else autotune.DEFAULT_BUCKET_BYTES)
        except Exception:
            logger.debug("grad-signature key component unavailable",
                         exc_info=True)
    return comps


def adopt_step(step_fn: Any, args: Tuple[Any, ...], *,
               label: str = "train_step",
               kind: str = "step",
               extra_components: Optional[Dict[str, Any]] = None
               ) -> Tuple[Callable, str]:
    """Serve a step function's AOT compile from the store.

    The step is traced + lowered HERE either way — the lowered text's
    content hash is part of the key, so a code-only edit to the step
    can never adopt a stale executable; what a HIT skips is the
    expensive XLA compile. On a MISS the lowered program is compiled
    NOW (the compile the first dispatch would have paid anyway —
    carved into the goodput ``compile`` phase) and published. Outcomes:
    ``hit | miss | disabled | unsupported | error``; everything except
    ``hit``/``miss`` returns ``step_fn`` unchanged."""
    store = from_env()
    if store is None:
        return step_fn, "disabled"
    if not hasattr(step_fn, "lower"):
        return step_fn, "unsupported"
    try:
        lowered = step_fn.lower(*args)
        comps = step_key_components(step_fn, args, lowered=lowered)
    except Exception as e:
        logger.warning("artifact store: step key unavailable (%s: %s); "
                       "store bypassed", type(e).__name__, e)
        return step_fn, "error"
    if extra_components:
        comps.update(extra_components)
    order_tag = comps["step"]
    # `kind` partitions the key space per consumer family: the serving
    # engine publishes under "serve" so a serve replica's warm boot and a
    # train step's resume can never collide on a digest, and store
    # operators can reason about entries by origin.
    key = store.key(kind, **comps)
    compiled = store.load_executable(key, order_tag=order_tag)
    if compiled is not None:
        logger.info("artifact store: %s served from %s (key %s) — "
                    "compile skipped", label, store.root, key.digest)
        return wrap_compiled(compiled, step_fn, label), "hit"
    try:
        t0 = time.perf_counter()
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
    except Exception as e:
        logger.warning("artifact store: AOT compile of %s failed "
                       "(%s: %s); jit dispatch path keeps working",
                       label, type(e).__name__, e)
        return step_fn, "error"
    from horovod_tpu.goodput import accountant as _goodput
    _goodput.carve(_goodput.COMPILE, dt)
    store.publish_executable(key, compiled, compile_seconds=dt,
                             order_tag=order_tag,
                             extra_meta={"label": label})
    return wrap_compiled(compiled, step_fn, label), "miss"
