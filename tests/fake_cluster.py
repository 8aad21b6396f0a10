"""Process-backed test doubles for the cluster substrates the image lacks.

The reference exercises its Spark runner against a local Spark session and
its Ray executor against a local Ray cluster (reference:
test/integration/test_spark.py, test/single/test_ray.py). Neither pyspark
nor ray is installed here, so these doubles supply the *exact API surface*
the integrations touch — BarrierTaskContext for spark._barrier_mapper, the
remote/get/kill actor API for RayExecutor._start_ray — while staying
faithful to the real substrates' process model: every barrier task / actor
runs in its OWN spawned process and the worlds they form via
``jax.distributed`` are real multi-process worlds.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import signal
import socket
import subprocess
import sys
import time
import traceback
import types
from typing import Any, Dict, List, Optional

try:
    import cloudpickle as _pickle
except ImportError:               # pragma: no cover
    import pickle as _pickle


def _child_jax_cpu():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("XLA_FLAGS", None)    # 1 CPU device per process
    import jax
    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# fake pyspark: barrier stage with one spawned process per partition
# ---------------------------------------------------------------------------

def make_fake_pyspark(partition_id=None, barrier=None, addresses=None):
    """A module object exposing exactly what the integration imports:
    ``pyspark.BarrierTaskContext`` (task side) and ``pyspark.sql.
    SparkSession`` (driver side, unused when a context is passed)."""
    pyspark = types.ModuleType("pyspark")
    pyspark_sql = types.ModuleType("pyspark.sql")

    class _TaskInfo:
        def __init__(self, address):
            self.address = address

    class BarrierTaskContext:
        @classmethod
        def get(cls):
            return cls()

        def partitionId(self):
            return partition_id

        def getTaskInfos(self):
            return [_TaskInfo(a) for a in addresses]

        def barrier(self):
            barrier.wait()

    class SparkSession:                      # driver-side import only
        class builder:
            @staticmethod
            def getOrCreate():
                raise RuntimeError("fake SparkSession cannot build")

    pyspark.BarrierTaskContext = BarrierTaskContext
    pyspark_sql.SparkSession = SparkSession
    pyspark.sql = pyspark_sql
    return pyspark, pyspark_sql


def install_fake_pyspark(monkeypatch):
    """Driver-process install so ``integrations.spark.run`` imports
    succeed (tasks install their own per-partition instance)."""
    pyspark, pyspark_sql = make_fake_pyspark()
    monkeypatch.setitem(sys.modules, "pyspark", pyspark)
    monkeypatch.setitem(sys.modules, "pyspark.sql", pyspark_sql)


def _spark_task_main(partition_id, barrier, addresses, mapper_payload,
                     conn):
    try:
        _child_jax_cpu()
        pyspark, pyspark_sql = make_fake_pyspark(partition_id, barrier,
                                                 addresses)
        sys.modules["pyspark"] = pyspark
        sys.modules["pyspark.sql"] = pyspark_sql
        mapper = _pickle.loads(mapper_payload)
        conn.send(("ok", list(mapper(iter([partition_id])))))
    except BaseException:
        conn.send(("error", traceback.format_exc()))
    finally:
        conn.close()


class FakeSparkContext:
    """The SparkContext surface spark.run touches:
    ``parallelize(...).barrier().mapPartitions(m).collect()``, with each
    partition executing in its own spawned process (executor-faithful)."""

    def __init__(self, default_parallelism: int = 2):
        self.defaultParallelism = default_parallelism

    def parallelize(self, data, num_slices):
        return _FakeRDD(num_slices)


class _FakeRDD:
    def __init__(self, num: int):
        self._num = num

    def barrier(self):
        return self

    def mapPartitions(self, mapper):
        return _FakeBarrierJob(self._num, mapper)


class _FakeBarrierJob:
    def __init__(self, num: int, mapper):
        self._num = num
        self._mapper = mapper

    def collect(self, timeout: float = 240.0) -> List[Any]:
        ctx = mp.get_context("spawn")
        barrier = ctx.Barrier(self._num)
        addresses = [f"127.0.0.1:{40000 + i}" for i in range(self._num)]
        payload = _pickle.dumps(self._mapper)
        procs, conns = [], []
        for pid in range(self._num):
            parent, child = ctx.Pipe(duplex=False)
            p = ctx.Process(target=_spark_task_main,
                            args=(pid, barrier, addresses, payload, child),
                            daemon=True)
            p.start()
            child.close()
            procs.append(p)
            conns.append(parent)
        results, errors = [], []
        for pid, conn in enumerate(conns):
            if not conn.poll(timeout):
                errors.append(f"task {pid}: timeout")
                continue
            status, value = conn.recv()
            (results.extend if status == "ok" else errors.append)(value)
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        if errors:
            raise RuntimeError("barrier stage failed:\n" + "\n".join(errors))
        return results


# ---------------------------------------------------------------------------
# ProcessWorld: an N-process jax.distributed CPU world for the resilience/
# chaos harness — real OS processes (kill -9 able, preemptable by signal or
# sentinel), one CPU device each, rendezvoused exactly like a launched run
# (HVD_TPU_COORDINATOR env -> hvd.init -> jax.distributed.initialize), so
# the coordination-service KV store the checkpoint commit barrier and the
# preemption quiesce protocol ride on is the real one.
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ProcessWorld:
    """Spawn ``script`` as ``nproc`` coordinated worker processes.

    Faithful to the process model the chaos tests must exercise: each
    worker can be SIGKILLed mid-step (``kill(rank)``), delivered a real
    SIGTERM (``terminate(rank)``), or left to exit on its own; exit codes
    are observable per rank (``wait()``/``poll()``). Restarting a world
    is just constructing a new ProcessWorld over the same state
    directories — which is exactly what a supervisor does."""

    def __init__(self, script: str, nproc: int,
                 env: Optional[Dict[str, str]] = None,
                 capture: bool = True):
        self.script = script
        self.nproc = nproc
        self.coordinator = f"127.0.0.1:{_free_port()}"
        self.extra_env = dict(env or {})
        self.capture = capture
        self.procs: List[subprocess.Popen] = []

    def start(self) -> "ProcessWorld":
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for pid in range(self.nproc):
            env = dict(os.environ)
            env.update(self.extra_env)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "HVD_TPU_COORDINATOR": self.coordinator,
                "HVD_TPU_NUM_PROCESSES": str(self.nproc),
                "HVD_TPU_PROCESS_ID": str(pid),
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            })
            out = subprocess.PIPE if self.capture else None
            self.procs.append(subprocess.Popen(
                [sys.executable, "-u", self.script], env=env,
                stdout=out, stderr=subprocess.STDOUT if out else None,
                text=bool(out)))
        return self

    def kill(self, rank: int, sig: int = signal.SIGKILL) -> None:
        self.procs[rank].send_signal(sig)

    def terminate(self, rank: int) -> None:
        self.kill(rank, signal.SIGTERM)

    def poll(self) -> List[Optional[int]]:
        return [p.poll() for p in self.procs]

    def wait(self, timeout: float = 180.0) -> List[int]:
        """Return codes by rank; stragglers past ``timeout`` are killed
        and reported as -9."""
        deadline = time.monotonic() + timeout
        for p in self.procs:
            left = max(deadline - time.monotonic(), 0.1)
            try:
                p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
        return [p.returncode for p in self.procs]

    def output(self, rank: int) -> str:
        p = self.procs[rank]
        if p.stdout is None:
            return ""
        return p.stdout.read() or ""

    def shutdown(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
            if p.stdout is not None:
                p.stdout.close()


# ---------------------------------------------------------------------------
# fake ray: remote/get/kill with one spawned process per actor
# ---------------------------------------------------------------------------

def _actor_server_main(cls_payload, init_payload, conn):
    try:
        _child_jax_cpu()
        cls = _pickle.loads(cls_payload)
        args, kwargs = _pickle.loads(init_payload)
        obj = cls(*args, **kwargs)
        conn.send(("up", None))
        while True:
            msg = conn.recv()
            if msg is None:
                break
            method, payload = msg
            try:
                args, kwargs = _pickle.loads(payload)
                conn.send(("ok", getattr(obj, method)(*args, **kwargs)))
            except BaseException:
                conn.send(("error", traceback.format_exc()))
    except BaseException:
        try:
            conn.send(("error", traceback.format_exc()))
        except Exception:
            pass
    finally:
        conn.close()


class _FakeFuture:
    """Per-actor pipes are FIFO with one outstanding call in the executor's
    flows, so a future is just 'the next reply on this actor's pipe'."""

    def __init__(self, conn):
        self._conn = conn

    def result(self):
        status, value = self._conn.recv()
        if status != "ok":
            raise RuntimeError(value)
        return value


class _FakeMethod:
    def __init__(self, handle, name):
        self._handle = handle
        self._name = name

    def remote(self, *args, **kwargs):
        self._handle._conn.send((self._name,
                                 _pickle.dumps((args, kwargs))))
        return _FakeFuture(self._handle._conn)


class _FakeActorHandle:
    def __init__(self, cls, args, kwargs, start_timeout: float = 120.0):
        ctx = mp.get_context("spawn")
        parent, child = ctx.Pipe(duplex=True)
        self._conn = parent
        self._proc = ctx.Process(
            target=_actor_server_main,
            args=(_pickle.dumps(cls), _pickle.dumps((args, kwargs)), child),
            daemon=True)
        self._proc.start()
        child.close()
        if not parent.poll(start_timeout):
            self._proc.terminate()
            raise TimeoutError("fake actor did not start")
        status, value = parent.recv()
        if status != "up":
            raise RuntimeError(value)

    def __getattr__(self, name):
        return _FakeMethod(self, name)


class _FakeActorClass:
    def __init__(self, cls):
        self._cls = cls

    def remote(self, *args, **kwargs):
        return _FakeActorHandle(self._cls, args, kwargs)


class FakeRay:
    """The slice of the ray module RayExecutor uses: is_initialized,
    remote (decorator, with or without options), get, kill."""

    def is_initialized(self):
        return True

    def remote(self, *args, **kwargs):
        if args and isinstance(args[0], type):
            return _FakeActorClass(args[0])

        def deco(cls):
            return _FakeActorClass(cls)
        return deco

    def get(self, x):
        if isinstance(x, list):
            return [self.get(v) for v in x]
        return x.result()

    def kill(self, handle):
        try:
            handle._conn.send(None)
        except Exception:
            pass
        handle._proc.join(timeout=5)
        if handle._proc.is_alive():
            handle._proc.terminate()
