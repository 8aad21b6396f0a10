"""Shared access to the jax.distributed coordination-service KV store.

This is the multi-controller control-plane transport (the role the
reference's MPI/Gloo controller plays for negotiation traffic,
mpi_controller.cc): the same service that rendezvoused the mesh, so it is
reachable exactly when cross-host synchronization is needed.

Every consumer goes through :func:`distributed_kv`, which returns the
raw :class:`DistributedKV` wrapped in ``resilience.faults.RetryingKV``
under the caller's named call-site policy (``site=``): transient
transport failures are retried with capped backoff + deterministic
jitter, exhausted budgets on optional sites degrade the fault domain
instead of killing the run, and protocol-critical sites fail loudly.
The nine consumers and their sites are cataloged in
``resilience.faults.KV_CONSUMER_SITES`` / docs/resilience.md. Chaos
injection (``resilience.chaos.on_kv``) happens HERE, beneath the retry
layer, so the chaos tier exercises the production recovery machinery.
"""

from __future__ import annotations

import threading
from typing import Optional, Set

from horovod_tpu.utils.logging import get_logger

logger = get_logger("horovod_tpu.utils.kvstore")

# delete() failures are logged once per key-class (the key minus its
# last path component — 'hvd/divcheck/g0/d7/p1' -> 'hvd/divcheck/g0/d7')
# and counted always; a long run's cleanup noise must not bury real
# failures, but the FIRST failure of a class is signal.
_delete_warned: Set[str] = set()
_delete_warned_lock = threading.Lock()


def _key_class(key: str) -> str:
    return key.rsplit("/", 1)[0] if "/" in key else key


def _chaos():
    from horovod_tpu.resilience import chaos
    return chaos


class DistributedKV:
    """Thin wrapper over the coordination-service client: blocking get,
    non-blocking try_get, set, best-effort delete."""

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str, overwrite: bool = False) -> None:
        """Write a key. The coordination-service store is write-once by
        default; ``overwrite=True`` is for periodically-republished keys
        (metrics snapshots) — unique-key consumers (autotune, divergence)
        keep the default so an accidental reuse still fails loudly."""
        _chaos().on_kv("set", key)
        if overwrite:
            try:
                self._client.key_value_set(key, value, allow_overwrite=True)
                return
            except TypeError:       # pragma: no cover - very old client
                self.delete(key)
        self._client.key_value_set(key, value)

    def get(self, key: str, timeout_s: float) -> str:
        """Blocking fetch; raises on timeout."""
        _chaos().on_kv("get", key)
        return self._client.blocking_key_value_get(
            key, int(timeout_s * 1000))

    def try_get(self, key: str) -> Optional[str]:
        """Non-blocking fetch; None when the key does not exist yet.
        Transport failures (dead coordination service) propagate — they
        must not masquerade as 'peer not there yet'."""
        _chaos().on_kv("try_get", key)
        try:
            return self._client.key_value_try_get(key)
        except Exception as e:
            if "NOT_FOUND" in str(e).upper().replace(" ", "_"):
                return None
            raise

    def delete(self, key: str) -> None:
        """Best-effort cleanup (bounds KV growth over long runs).
        Failures never raise — but they are no longer silent: each is
        counted (``hvd_kvstore_delete_failures_total``) and the first
        failure per key-class is logged, so a coordination service that
        stopped accepting deletes (unbounded KV growth on a long run)
        is visible in /metrics instead of discovered at OOM."""
        try:
            _chaos().on_kv("delete", key)
            self._client.key_value_delete(key)
        except Exception:
            kc = _key_class(key)
            try:
                from horovod_tpu import metrics as M
                M.counter(
                    "hvd_kvstore_delete_failures_total",
                    "Best-effort KV deletes that errored (cleanup only "
                    "— keys leak until the service forgets them)",
                    labelnames=("key_class",)).labels(key_class=kc).inc()
            except Exception:       # metrics plane not up
                pass
            with _delete_warned_lock:
                first = kc not in _delete_warned
                if first:
                    _delete_warned.add(kc)
            if first:
                logger.warning(
                    "KV delete failed for key class %r (logged once per "
                    "class; every failure counts toward "
                    "hvd_kvstore_delete_failures_total)", kc,
                    exc_info=True)


def distributed_kv(site: str = "kv"):
    """The process's coordination-service KV store wrapped in the
    ``site``'s retry policy (resilience.faults.RetryingKV), or None
    outside a multi-controller run (jax.distributed.initialize not
    called).

    The SchedulerHooks seam may inject a substitute client (hvdmodel's
    simulated coordination service); the wrapper stack — retry policy,
    NOT_FOUND mapping, best-effort delete — is the same real code
    either way, which is exactly what lets the model checker explore
    retry interleavings through production logic."""
    from horovod_tpu.resilience.faults import RetryingKV
    from horovod_tpu.utils import schedhooks
    injected = schedhooks.hooks().kv_client()
    if injected is not None:
        return RetryingKV(DistributedKV(injected), site=site)
    try:
        # No public accessor for the coordination-service client exists
        # in jax 0.9.0; this private one is still present there.
        from jax._src.distributed import global_state
        client = global_state.client
    except Exception:       # pragma: no cover - jax internals moved
        return None
    if client is None:
        return None
    return RetryingKV(DistributedKV(client), site=site)
