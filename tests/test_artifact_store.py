"""hvdstore — the persistent compiled-artifact store (ISSUE 13).

Unit tier: entry round trips, a MISS for every composite-fingerprint
component (flipped knob / changed mesh / changed gradient payload /
stale collective order / version skew — a stale executable can never
load), corrupt/truncated artifacts falling back to recompile, the
size-budgeted mtime-LRU eviction, concurrent readers, the crash-safe
atomic publish under the schedhooks seam, chaos ``store_corrupt``,
fault-domain shedding, and the consumer integrations (ExecutableCache,
adopt_step, the bucket-auto warm path). The cross-process kill→resume
acceptance e2e lives in tests/test_chaos_e2e.py.
"""

import json
import os
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.config import knobs
from horovod_tpu.store import artifact_store as st
from horovod_tpu.utils import schedhooks


@pytest.fixture()
def store(tmp_path):
    knobs.set_override("HOROVOD_ARTIFACT_STORE", str(tmp_path / "store"))
    st.reset_for_tests()
    yield st.from_env()
    knobs.clear_override("HOROVOD_ARTIFACT_STORE")
    st.reset_for_tests()


def _compiled(c=2.0):
    f = jax.jit(lambda x: x * c + 1)
    return st.aot_compile(f, (jnp.arange(8.0),))


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

def test_executable_round_trip(store):
    compiled, dt = _compiled()
    key = store.key("step", sig="rt", knobs=st.program_knob_fingerprint())
    assert store.publish_executable(key, compiled, compile_seconds=dt)
    loaded = store.load_executable(key)
    assert loaded is not None
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(loaded(x)),
                                  np.asarray(x * 2 + 1))
    s = store.stats()
    assert s["hits"] == 1 and s["publishes"] == 1
    assert s["compile_seconds_saved"] > 0      # publish-time measured cost


@pytest.mark.parametrize("device_ids", [[5], [2, 3]])
def test_sub_mesh_executable_loads_onto_its_own_devices(store, device_ids):
    """A program compiled for ONE device, or a 2-device sub-mesh, of the
    8-device process: the store-loaded executable runs on exactly those
    devices (not on every device of the backend) and is bitwise the
    fresh compile. Two placements of one program are two entries."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def place(ids):
        mesh = Mesh(np.array([jax.devices()[i] for i in ids]), ("x",))
        return jax.device_put(jnp.arange(64.0).reshape(8, 8),
                              NamedSharding(mesh, P("x")))

    f = jax.jit(lambda x: jnp.tanh(x @ x.T) * 3, donate_argnums=0)
    want = np.asarray(f.lower(place(device_ids)).compile()(place(device_ids)))
    cold, outcome = st.adopt_step(f, (place(device_ids),), kind="serve")
    assert outcome == "miss"
    warm, outcome = st.adopt_step(f, (place(device_ids),), kind="serve")
    assert outcome == "hit"
    out = warm(place(device_ids))
    assert sorted(d.id for d in out.sharding.device_set) == device_ids
    np.testing.assert_array_equal(np.asarray(out), want)
    assert not warm.hvd_store_rejected      # no jit fall-back was taken
    # the same program on other devices is another executable
    other = [i + 1 for i in device_ids]
    moved, outcome = st.adopt_step(f, (place(other),), kind="serve")
    assert outcome == "miss"
    out = moved(place(other))
    assert sorted(d.id for d in out.sharding.device_set) == other
    np.testing.assert_array_equal(np.asarray(out), want)


def test_tpu_sub_mesh_programs_are_not_reloaded():
    """One chip or the whole backend reload on TPU; a program over some
    of the chips halted the core when reloaded (chip run, PR 21), so it
    recompiles. Other platforms reload anything."""
    assert st.reloadable("tpu", 1, 4) and st.reloadable("tpu", 4, 4)
    assert not st.reloadable("tpu", 2, 4)
    assert not st.reloadable("tpu", 3, 4)
    assert st.reloadable("cpu", 2, 8)


def test_blob_round_trip(store):
    key = store.key("bucket_auto_sweep", grad_signature="g", workload="w")
    obj = {"winner_bucket_bytes": 123, "candidates": {"1": {"s": 0.5}}}
    assert store.publish_blob(key, obj)
    assert store.load_blob(key) == obj


def test_disabled_store_is_none():
    st.reset_for_tests()
    knobs.set_override("HOROVOD_ARTIFACT_STORE", "")
    try:
        assert st.from_env() is None
        assert st.store_stats() is None
        f = jax.jit(lambda x: x + 1)
        fn, outcome = st.adopt_step(f, (jnp.arange(4.0),))
        assert outcome == "disabled" and fn is f
    finally:
        knobs.clear_override("HOROVOD_ARTIFACT_STORE")
        st.reset_for_tests()


# ---------------------------------------------------------------------------
# per-component key misses — a stale executable can never load
# ---------------------------------------------------------------------------

def test_flipped_knob_misses(store):
    compiled, _ = _compiled()
    key = store.key("step", knobs=st.program_knob_fingerprint())
    store.publish_executable(key, compiled)
    knobs.set_override("HOROVOD_GRADIENT_COMPRESSION", "fp8_e4m3")
    try:
        flipped = store.key("step", knobs=st.program_knob_fingerprint())
        assert flipped.digest != key.digest
        assert store.load_executable(flipped) is None
    finally:
        knobs.clear_override("HOROVOD_GRADIENT_COMPRESSION")
    assert store.load_executable(key) is not None


def test_changed_mesh_misses(store):
    compiled, _ = _compiled()
    mesh_a = {"world_size": 1, "n_devices": 8, "mesh_shape": [8]}
    mesh_b = {"world_size": 1, "n_devices": 8, "mesh_shape": [2, 4]}
    key = store.key("step", mesh=mesh_a)
    store.publish_executable(key, compiled)
    changed = store.key("step", mesh=mesh_b)
    assert changed.digest != key.digest
    assert store.load_executable(changed) is None


def test_changed_grad_signature_misses(store):
    from horovod_tpu.autotune import grad_signature
    compiled, _ = _compiled()
    sig_a = grad_signature([((16, 4), jnp.dtype(jnp.float32))], 8)
    sig_b = grad_signature([((16, 8), jnp.dtype(jnp.float32))], 8)
    key = store.key("step", grad_signature=sig_a)
    store.publish_executable(key, compiled)
    assert store.load_executable(store.key(
        "step", grad_signature=sig_b)) is None
    assert store.load_executable(key) is not None


def test_changed_collective_order_misses(store):
    """HVD503 continuity: when this process already verified a program
    under the tag and the stored schedule identity disagrees, the entry
    is stale — it must MISS, never load."""
    from horovod_tpu.analysis import ir
    compiled, _ = _compiled()
    tag = "step_fn@deadbeef0000"
    key = store.key("step", step=tag)
    assert store.publish_executable(key, compiled, order_tag=tag)
    try:
        # entry loads while the live registry agrees/knows nothing
        assert store.load_executable(key, order_tag=tag) is not None
        # a DIFFERENT verified order under the same tag -> stale miss
        ir._reset_order_registry()
        ir.record_order(tag, [{"kind": "all-reduce", "shape": "f32[9]",
                               "replica_groups": "{}"}])
        assert store.load_executable(key, order_tag=tag) is None
    finally:
        ir._reset_order_registry()


def test_code_only_edit_misses(store):
    """A code-only change to the step — same symbol, same shapes, same
    knobs, same mesh — must MISS: the key carries the LOWERED program's
    content hash, so editing the loss can never adopt the old model's
    executable."""
    x = jnp.arange(8.0)

    def make(scale):
        def step(s, v):
            return s + jnp.sum(v * scale)
        return jax.jit(step)

    args = (jnp.float32(0.0), x)
    assert st.adopt_step(make(2.0), args)[1] == "miss"
    assert st.adopt_step(make(2.0), args)[1] == "hit"
    # the edited program (scale 3.0) shares symbol/shapes/knobs but NOT
    # the lowered text — it must compile fresh, not adopt scale 2.0
    fn_b, outcome = st.adopt_step(make(3.0), args)
    assert outcome == "miss"
    np.testing.assert_array_equal(
        np.asarray(fn_b(*args)), np.asarray(jnp.sum(x * 3.0)))


def test_fs_transient_store_scope_and_separate_budget(store):
    """chaos fs_transient: 'scope': 'store' drills the store's fs
    points (retry_fs absorbs the EIO) with its OWN injection budget;
    the default checkpoint scope never touches store I/O."""
    from horovod_tpu.resilience import chaos, faults
    compiled, _ = _compiled()
    key = store.key("step", sig="fs-scope")
    store.publish_executable(key, compiled)
    faults.reset_for_tests()
    chaos.install({"fs_transient": {"fail_first": 1, "scope": "store"}})
    try:
        spec = chaos.active()
        assert store.load_executable(key) is not None   # EIO absorbed
        assert spec._store_fs_failed == 1
        assert spec._fs_failed == 0                     # ckpt untouched
    finally:
        chaos.install(None)
    chaos.install({"fs_transient": {"fail_first": 1}})  # default scope
    try:
        spec = chaos.active()
        assert store.load_executable(key) is not None
        assert spec._store_fs_ops == 0      # store ops never consulted
        assert spec._fs_failed == 0         # ckpt budget not consumed
    finally:
        chaos.install(None)
        faults.reset_for_tests()


def test_version_skew_misses_and_logs(store):
    compiled, _ = _compiled()
    key = store.key("step", sig="skew")
    store.publish_executable(key, compiled)
    # rewrite the committed entry's header with a foreign jax version
    path = store._path(key)
    raw = open(path, "rb").read()
    (hlen,) = struct.unpack(">I", raw[len(st.MAGIC):len(st.MAGIC) + 4])
    body = raw[len(st.MAGIC) + 4:]
    header = json.loads(body[:hlen])
    header["env"] = dict(header["env"], jax="0.0.1-foreign")
    hdr = json.dumps(header, sort_keys=True).encode()
    open(path, "wb").write(
        st.MAGIC + struct.pack(">I", len(hdr)) + hdr + body[hlen:])
    misses_before = store.stats()["misses"]
    assert store.load_executable(key) is None
    s = store.stats()
    assert s["misses"] == misses_before + 1
    assert os.path.exists(path)       # skewed entries are kept (evicted
    #                                   later by the LRU), not deleted


# ---------------------------------------------------------------------------
# robustness: corrupt/truncated artifacts recompile, never crash
# ---------------------------------------------------------------------------

def test_corrupt_and_truncated_fall_back(store):
    compiled, _ = _compiled()
    key = store.key("step", sig="corrupt")
    store.publish_executable(key, compiled)
    path = store._path(key)
    raw = open(path, "rb").read()
    for mutation in (
            raw[: len(raw) // 2],                     # truncated payload
            raw[: len(st.MAGIC) + 2],                 # truncated header
            b"GARBAGE" + raw[7:],                     # bad magic
            raw[: -8] + b"\x00" * 8,                  # flipped payload bits
            b""):                                     # empty file
        open(path, "wb").write(mutation)
        assert store.load_executable(key) is None     # never raises
    open(path, "wb").write(raw)
    assert store.load_executable(key) is not None
    misses = store.stats()["misses"]
    assert misses >= 5


def test_chaos_store_corrupt_falls_back(store):
    from horovod_tpu.resilience import chaos
    compiled, _ = _compiled()
    key = store.key("step", sig="chaos")
    store.publish_executable(key, compiled)
    chaos.install({"store_corrupt": {"fail_first": 1}})
    try:
        assert store.load_executable(key) is None     # injected bit-rot
        assert store.load_executable(key) is not None  # budget spent
    finally:
        chaos.install(None)


def test_shed_site_compiles_as_usual(store):
    """artifact_store is an OPTIONAL fault-domain site: while shed, the
    store answers None/False (compile as usual) instead of touching the
    filesystem, and /healthz turns degraded — never a crash."""
    from horovod_tpu.resilience import faults
    compiled, _ = _compiled()
    key = store.key("step", sig="shed")
    assert "artifact_store" in faults.SHEDDABLE_SITES
    faults.reset_for_tests()
    knobs.set_override("HOROVOD_FAULT_PROBE_SECONDS", 9999)
    try:
        faults.fault_domain().record_exhausted("artifact_store",
                                               critical=False)
        assert faults.fault_domain().state() == faults.DEGRADED
        assert not store.publish_executable(key, compiled)
        assert store.load_executable(key) is None
        assert store.stats()["shed"] >= 2
        faults.fault_domain().record_success("artifact_store")
        assert store.publish_executable(key, compiled)
        assert store.load_executable(key) is not None
    finally:
        knobs.clear_override("HOROVOD_FAULT_PROBE_SECONDS")
        faults.reset_for_tests()


# ---------------------------------------------------------------------------
# eviction + concurrency + atomic publish
# ---------------------------------------------------------------------------

def test_lru_eviction_by_mtime(tmp_path):
    knobs.set_override("HOROVOD_ARTIFACT_STORE", str(tmp_path / "s"))
    st.reset_for_tests()
    try:
        store = st.from_env()
        keys = [store.key("blob", i=i) for i in range(3)]
        payload = {"x": "y" * 512}
        store.publish_blob(keys[0], payload)
        store.publish_blob(keys[1], payload)
        # entry 0 is HOT (touched -> newest mtime); entry 1 is cold
        now = time.time()
        os.utime(store._path(keys[0]), (now, now))
        os.utime(store._path(keys[1]), (now - 1000, now - 1000))
        entry_size = os.path.getsize(store._path(keys[0]))
        store.max_bytes = entry_size * 2 + 10     # room for exactly two
        store.publish_blob(keys[2], payload)
        assert not store.contains(keys[1])        # oldest mtime evicted
        assert store.contains(keys[0]) and store.contains(keys[2])
        assert store.stats()["evictions"] == 1
    finally:
        knobs.clear_override("HOROVOD_ARTIFACT_STORE")
        st.reset_for_tests()


def test_concurrent_readers(store):
    compiled, _ = _compiled()
    key = store.key("step", sig="conc")
    store.publish_executable(key, compiled)
    x = jnp.arange(8.0)
    want = np.asarray(x * 2 + 1)
    errs = []

    def reader():
        try:
            for _ in range(5):
                loaded = store.load_executable(key)
                assert loaded is not None
                np.testing.assert_array_equal(np.asarray(loaded(x)), want)
        except Exception as e:                      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs


def test_atomic_publish_under_schedhooks_seam(store):
    """Kill-mid-publish drill: the publish's ONE rename is routed
    through the schedhooks seam; a crash at that point leaves only a
    ``.tmp-`` file, which readers ignore, eviction scans skip, and a
    later publish replaces — the store never serves a partial entry."""
    compiled, _ = _compiled()
    key = store.key("step", sig="atomic")
    renames = []

    class CrashAtPublish(schedhooks.SchedulerHooks):
        def rename(self, src, dst):
            renames.append((src, dst))
            raise KeyboardInterrupt("simulated kill at the publish point")

    prev = schedhooks.install(CrashAtPublish())
    try:
        with pytest.raises(KeyboardInterrupt):
            store.publish_executable(key, compiled)
    finally:
        schedhooks.install(prev)
    # the interrupted publish staged everything in a .tmp- sibling
    (src, dst) = renames[0]
    assert os.path.basename(src).startswith(".tmp-")
    assert dst == store._path(key)
    assert os.path.exists(src)                   # the "crash" left it
    # readers: the entry is ABSENT (no partial visible), not corrupt
    assert not store.contains(key)
    assert store.load_executable(key) is None
    assert all(nb >= 0 and not p.endswith(src)
               for p, nb, _ in store._entries())
    # stale tmp files are reaped once old
    os.utime(src, (time.time() - 7200, time.time() - 7200))
    store._entries()
    assert not os.path.exists(src)
    # a later publish of the same key succeeds and loads
    assert store.publish_executable(key, compiled)
    assert store.load_executable(key) is not None


# ---------------------------------------------------------------------------
# consumers
# ---------------------------------------------------------------------------

def test_executable_cache_warm_process_builds_nothing(store):
    """Consumer 1: two ExecutableCache 'incarnations' against one store
    — the second pays ZERO builder invocations (the store-smoke CI
    assertion, in-process)."""
    from horovod_tpu.ops.coordinator import ExecutableCache
    x = jnp.arange(8.0)
    sig = ("allreduce", "sum", ((8,),), ("float32",))

    def make_builder(calls):
        def builder():
            calls.append(1)
            return jax.jit(lambda v: v * 3)
        return builder

    cold_calls, warm_calls = [], []
    cold = ExecutableCache(capacity=8)
    fn = cold.get_or_build(sig, make_builder(cold_calls), store_args=(x,))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x * 3))
    assert cold.snapshot()["builds"] == 1 and len(cold_calls) == 1

    warm = ExecutableCache(capacity=8)     # fresh in-memory cache
    fn2 = warm.get_or_build(sig, make_builder(warm_calls),
                            store_args=(x,))
    np.testing.assert_array_equal(np.asarray(fn2(x)), np.asarray(x * 3))
    snap = warm.snapshot()
    assert snap["builds"] == 0 and snap["store_hits"] == 1
    assert not warm_calls


def test_adopt_step_hit_is_bitwise_identical(store):
    """Consumer 2: a fresh jit closure adopting the stored executable
    produces a BITWISE-identical trajectory to the jit path."""
    def make_step():
        return jax.jit(lambda s, x: (s + jnp.sum(x * s), jnp.mean(x)))

    s0 = jnp.float32(1.5)
    xs = [jnp.arange(6.0) * (i + 1) for i in range(4)]

    def run(fn):
        s = s0
        for x in xs:
            s, _ = fn(s, x)
        return np.asarray(s)

    ref = run(make_step())
    miss_fn, outcome = st.adopt_step(make_step(), (s0, xs[0]))
    assert outcome == "miss"
    warm_fn, outcome2 = st.adopt_step(make_step(), (s0, xs[0]))
    assert outcome2 == "hit"
    assert hasattr(warm_fn, "hvd_store_compiled")
    np.testing.assert_array_equal(run(miss_fn), ref)
    np.testing.assert_array_equal(run(warm_fn), ref)


def test_adopt_step_rejection_falls_back_to_jit(store):
    f = jax.jit(lambda s, x: s + x)
    args = (jnp.float32(0.0), jnp.arange(4.0))
    st.adopt_step(f, args)
    warm_fn, outcome = st.adopt_step(jax.jit(lambda s, x: s + x), args)
    assert outcome == "hit"
    # different SHAPE -> the compiled entry rejects before execution and
    # the jit fallback takes over permanently
    out = warm_fn(jnp.float32(1.0), jnp.arange(16.0))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(16.0) + 1.0)


def test_train_loop_and_verify_share_one_entry(store):
    """Consumers 2+3: HOROVOD_VERIFY_STEP's compile and the train
    loop's adoption resolve the SAME key — verify-then-train across
    'restarts' pays one compile total."""
    import optax

    from horovod_tpu.analysis import ir
    from horovod_tpu.parallel import trainer

    hvd.init()
    try:
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Average)

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        init_fn, train_step, put_batch = \
            trainer.data_parallel_train_step(loss_fn, opt, mesh)
        state = init_fn({"w": jnp.zeros((8, 1), jnp.float32)})
        batch = put_batch((np.ones((8, 8), np.float32),
                           np.ones((8, 1), np.float32)))
        ir._reset_order_registry()
        _, report = ir.verify_report(train_step, (state, batch),
                                     mesh=mesh)
        assert report["artifact_store"] == "miss"   # published now
        ir._reset_order_registry()
        _, report2 = ir.verify_report(train_step, (state, batch),
                                      mesh=mesh)
        assert report2["artifact_store"] == "hit"
        # a FRESH jit of the same step adopts the verify entry
        init_fn2, train_step2, _ = \
            trainer.data_parallel_train_step(loss_fn, opt, mesh)
        _, outcome = st.adopt_step(train_step2, (state, batch))
        assert outcome == "hit"
        # the verify TAG is not key material — a custom-tag verify of
        # the same program (the bench --verify-report shape) shares the
        # entry too: the key is the program's identity, so
        # verify-then-train pays one compile total for every caller
        hits_before = store.stats()["hits"]
        _, report3 = ir.verify_report(train_step2, (state, batch),
                                      mesh=mesh, tag="custom-tag",
                                      check_determinism=False)
        assert report3["artifact_store"] == "hit"
        assert store.stats()["hits"] == hits_before + 1
    finally:
        hvd.shutdown()
        ir._reset_order_registry()


def test_bucket_auto_warm_skips_sweep(store):
    """Satellite: a completed bucket-auto sweep persists through the
    store; the warm path loads it (counter increments) instead of
    recompiling candidates."""
    from horovod_tpu import autotune, metrics as M
    sig = autotune.grad_signature([((64,), jnp.dtype(jnp.float32))], 8)
    record = {"n_devices": 8,
              "configs": {"0": {"gradient_all_reduces": 3}},
              "sweep": {"winner_bucket_bytes": 25 << 20,
                        "candidates": {str(25 << 20):
                                       {"exposed_comm_s": 0.1}}},
              "compression_sweep": {"bucket_bytes": 25 << 20}}
    assert autotune.load_auto_sweep(sig, "resnet50") is None
    assert autotune.persist_auto_sweep(sig, "resnet50", record)
    before = M.counter("hvd_bucket_auto_warm_hits_total", "").value
    warm = autotune.load_auto_sweep(sig, "resnet50")
    assert warm == record
    assert M.counter("hvd_bucket_auto_warm_hits_total",
                     "").value == before + 1
    # a different workload is a different key
    assert autotune.load_auto_sweep(sig, "transformer") is None


def test_overlap_report_warm_auto_runs_zero_compiles(
        store, tmp_path, monkeypatch):
    """bench.py --overlap-report under auto: after one (stubbed) cold
    sweep, the warm run performs ZERO _overlap_compile invocations and
    reproduces the same winner + artifact sections."""
    import bench
    from horovod_tpu import autotune

    MIB = 1 << 20
    compile_calls = []

    def fake_compile(topology, bucket_bytes, compression="none"):
        compile_calls.append((int(bucket_bytes or 0), compression))
        bb = int(bucket_bytes) if bucket_bytes else 100 * MIB
        total = 100 * MIB
        rows = []
        n = max(total // bb, 1)
        for i in range(n):
            rows.append({"bytes": bb, "schedule_line": i * 10,
                         "hideable_conv_fusions": min(i, 3),
                         "conv_fusions_total": 4})
        graph = {}
        for i, r in enumerate(rows):
            convs = []
            for j in range(r["conv_fusions_total"]):
                cname = f"%conv.{i}.{j}"
                graph[cname] = {"line": i * 1000 + j, "kind": "conv",
                                "bytes": 1, "operands": []}
                convs.append(cname)
            graph[f"%ar.{i}"] = {
                "line": i * 1000 + 999, "kind": "all-reduce",
                "bytes": int(r["bytes"]),
                "operands": convs[r["hideable_conv_fusions"]:]}
        return graph, True, 8

    sig = autotune.grad_signature([((10,), jnp.dtype(jnp.float32))], 8)
    monkeypatch.setattr(bench, "_overlap_compile", fake_compile)
    monkeypatch.setattr(bench, "_overlap_grad_signature", lambda n: sig)
    monkeypatch.setenv("HVD_OVERLAP_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_OVERLAP_TOPOLOGY", "v5e:2x4")
    monkeypatch.setenv("HOROVOD_BUCKET_AUTO_CACHE",
                       str(tmp_path / "bucket.json"))
    knobs.set_override("HOROVOD_GRADIENT_BUCKET_BYTES", "auto")
    try:
        assert bench.overlap_report_main() == 0
        cold_calls = len(compile_calls)
        assert cold_calls > 0
        cold_out = json.load(open(tmp_path / "OVERLAP.json"))
        assert "warm_from_store" not in cold_out["auto_sweep"]

        compile_calls.clear()
        assert bench.overlap_report_main() == 0
        assert compile_calls == []              # the satellite's claim
        warm_out = json.load(open(tmp_path / "OVERLAP.json"))
        assert warm_out["auto_sweep"]["warm_from_store"] is True
        assert warm_out["auto_sweep"]["winner_bucket_bytes"] \
            == cold_out["auto_sweep"]["winner_bucket_bytes"]
        assert warm_out["compression_sweep"]["warm_from_store"] is True
        assert set(warm_out["configs"]) == set(cold_out["configs"])
    finally:
        knobs.clear_override("HOROVOD_GRADIENT_BUCKET_BYTES")


def test_healthz_and_ledger_carry_store_block(store):
    compiled, dt = _compiled()
    key = store.key("step", sig="obs")
    store.publish_executable(key, compiled, compile_seconds=dt)
    store.load_executable(key)
    from horovod_tpu import metrics as M
    block = M.health_snapshot()["artifact_store"]
    assert block["hits"] >= 1 and block["publishes"] >= 1
    assert block["compile_seconds_saved"] > 0
    from horovod_tpu.goodput import ledger
    rec = ledger.build_record()
    assert rec["artifact_store"]["hits"] >= 1
