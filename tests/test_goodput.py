"""hvdgoodput — the time-attribution accountant (phases partition wall
time), the numerics-health detectors (golden streams, fusion-bucket
localization, flight recordings), the run ledger, and the cross-run
regression sentinel behind ``bench.py --regression-report``."""

import json
import os
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.config import knobs
from horovod_tpu.goodput import accountant, ledger, numerics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _goodput_state():
    yield
    knobs.clear_all_overrides()
    accountant.reset_for_tests()
    numerics.reset_for_tests()
    from horovod_tpu.resilience import faults
    faults.reset_for_tests()


def _enable_accounting():
    accountant.init_begin()
    accountant.init_end()


# ---------------------------------------------------------------------------
# the accountant: phases partition wall time
# ---------------------------------------------------------------------------

class TestAccountant:
    def test_phases_partition_total(self):
        _enable_accounting()
        accountant.set_phase(accountant.STEP_COMPUTE)
        time.sleep(0.02)
        accountant.set_phase(accountant.INPUT_WAIT)
        time.sleep(0.01)
        r = accountant.goodput_report()
        assert abs(r["attributed_seconds"] - r["total_seconds"]) \
            <= 0.01 * r["total_seconds"]
        assert set(r["phases"]) == set(accountant.PHASES)
        assert r["phases"]["step_compute"] >= 0.015
        assert r["phases"]["input_wait"] >= 0.005
        assert 0.0 <= r["goodput_fraction"] <= 1.0
        assert r["current_phase"] == "input_wait"

    def test_carve_preserves_total_and_clamps(self):
        _enable_accounting()
        accountant.set_phase(accountant.STEP_COMPUTE)
        time.sleep(0.02)
        # carve more than the bucket holds: clamped, total preserved
        moved = accountant.carve(accountant.EXPOSED_COLLECTIVE, 10.0)
        r = accountant.goodput_report()
        assert 0.0 < moved <= r["total_seconds"]
        assert abs(r["attributed_seconds"] - r["total_seconds"]) \
            <= 0.01 * r["total_seconds"]
        assert r["phases"]["exposed_collective"] == pytest.approx(
            moved, abs=1e-6)

    def test_phase_scope_restores(self):
        _enable_accounting()
        accountant.set_phase(accountant.STEP_COMPUTE)
        with accountant.phase_scope(accountant.CHECKPOINT):
            assert accountant.current_phase() == "checkpoint"
        assert accountant.current_phase() == "step_compute"

    def test_disabled_is_noop(self):
        assert accountant.current_phase() == "untracked"
        accountant.set_phase(accountant.IDLE)          # no-op, no raise
        assert accountant.carve(accountant.COMPILE, 1.0) == 0.0
        assert accountant.health_block() is None

    def test_unknown_phase_rejected(self):
        _enable_accounting()
        with pytest.raises(ValueError):
            accountant.get_accountant().set_phase("nonsense")


# ---------------------------------------------------------------------------
# surfaces: /healthz, metrics_snapshot, gauges, timeline cycle tags
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_health_and_snapshot_blocks(self, hvd_ctx):
        from horovod_tpu import metrics as M
        h = M.health_snapshot()
        assert "goodput" in h
        assert set(h["goodput"]) == {"fraction", "phase", "total_seconds"}
        snap = hvd.metrics_snapshot()
        assert "goodput" in snap
        assert snap["goodput"]["phases"]
        # Prometheus render skips the JSON-only block but serves the
        # gauges the scrape-time collector refreshes.
        text = M.render_snapshot(snap)
        assert "hvd_goodput_fraction" in text
        assert 'hvd_goodput_phase_seconds{phase="step_compute"}' in text
        assert "goodput{" not in text

    def test_merge_skips_goodput_block(self, hvd_ctx):
        from horovod_tpu import metrics as M
        snap = hvd.metrics_snapshot()
        merged = M.merge_snapshots([snap, snap])
        assert "goodput" not in merged
        assert "hvd_goodput_fraction" in M.render_snapshot(merged)

    def test_snapshot_dump_carries_goodput(self, hvd_ctx, tmp_path):
        from horovod_tpu import metrics as M
        dumper = M.SnapshotDumper(str(tmp_path / "m.json"), interval=60)
        dumper.stop()
        payload = json.loads((tmp_path / "m.json").read_text())
        assert "goodput" in payload["metrics"]
        assert "goodput" in payload["health"]

    def test_goodput_report_public_api(self, hvd_ctx):
        r = hvd.goodput_report()
        assert r["phases"]["init"] > 0         # hvd.init was attributed
        assert r["current_phase"] == "idle"

    def test_timeline_cycle_marker_carries_phase(self, hvd_ctx, tmp_path):
        from horovod_tpu.timeline import start_timeline, stop_timeline
        knobs.set_override("HOROVOD_TIMELINE_MARK_CYCLES", True)
        path = str(tmp_path / "tl.json")
        start_timeline(path)
        try:
            accountant.set_phase(accountant.STEP_COMPUTE)
            h = hvd.allreduce_async(np.ones((8, 4), np.float32),
                                    name="tl_cycle_probe")
            hvd.synchronize(h)
        finally:
            accountant.set_phase(accountant.IDLE)
            stop_timeline()
        events = json.loads(open(path).read())
        cycles = [e for e in events if e.get("name") == "CYCLE"]
        assert cycles, events
        assert all(e["args"]["phase"] == "step_compute" for e in cycles)


# ---------------------------------------------------------------------------
# numerics: golden streams
# ---------------------------------------------------------------------------

class TestDetectors:
    def test_loss_spike_golden_stream(self):
        det = numerics.LossSpikeDetector(sigma=6.0, warmup=10, alpha=0.1)
        rng = np.random.RandomState(0)
        stream = list(2.0 + 0.01 * rng.randn(30))
        fired = [i for i, v in enumerate(stream) if det.observe(v)]
        assert fired == []
        a = det.observe(8.0)                   # the spike
        assert a and a["kind"] == "loss_spike"
        assert a["value"] == 8.0
        # recovery values keep streaming without refiring forever
        assert det.observe(2.0) is None

    def test_loss_nonfinite_fires_immediately(self):
        det = numerics.LossSpikeDetector()
        a = det.observe(float("nan"))
        assert a and a["kind"] == "nonfinite" and a["signal"] == "loss"

    def test_grad_norm_explosion_golden_stream(self):
        det = numerics.GradNormDetector(factor=10.0, warmup=5, alpha=0.2)
        for _ in range(10):
            assert det.observe(1.0) is None
        a = det.observe(50.0)
        assert a and a["kind"] == "grad_norm_explosion"
        assert a["factor"] == 10.0

    def test_descending_loss_never_fires(self):
        det = numerics.LossSpikeDetector(sigma=6.0, warmup=5)
        for v in np.linspace(5.0, 0.5, 50):
            assert det.observe(float(v)) is None


class TestLocalization:
    def _grads(self):
        # three 1 KiB f32 leaves + one 2 KiB: bucket_bytes=2048 in
        # REVERSE order plans [d], [c, b], [a] -> buckets 0..2
        return {
            "a": np.zeros((256,), np.float32),
            "b": np.zeros((256,), np.float32),
            "c": np.zeros((256,), np.float32),
            "d": np.zeros((512,), np.float32),
        }

    def test_bucket_param_map_matches_fusion_plan(self):
        m = numerics.bucket_param_map(self._grads(), bucket_bytes=2048)
        named = {k: [n.strip("[']") for n in v] for k, v in m.items()}
        # reverse backward order: d fills bucket 0, then c+b, then a
        assert named == {0: ["d"], 1: ["c", "b"], 2: ["a"]}

    def test_nan_localized_to_correct_bucket(self):
        grads = self._grads()
        grads["b"][7] = np.nan                 # bucket 1
        out = numerics.localize_nonfinite(grads, bucket_bytes=2048)
        assert len(out) == 1
        assert out[0]["bucket"] == 1
        assert out[0]["nonfinite"] == 1
        assert any("b" in p for p in out[0]["params"])

    def test_all_finite_is_empty(self):
        assert numerics.localize_nonfinite(self._grads(),
                                           bucket_bytes=2048) == []

    def test_traced_helpers(self, hvd_ctx):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def summarize(g):
            return numerics.grad_summary(g)

        grads = {"w": jnp.ones((8, 8)), "b": jnp.full((4,), jnp.nan)}
        s = summarize(grads)
        assert int(np.sum(np.asarray(s["nonfinite"]))) == 4
        assert not np.isfinite(float(s["global_sq_norm"]))
        ratio = float(jax.jit(numerics.update_ratio)(
            {"w": jnp.ones((4,))}, {"w": jnp.full((4,), 0.01)}))
        assert ratio == pytest.approx(0.01, rel=1e-5)


# ---------------------------------------------------------------------------
# the monitor: flight recordings, actions, the eager coordinator feed
# ---------------------------------------------------------------------------

class TestMonitor:
    def _tracing(self, tmp_path):
        from horovod_tpu.tracing import spans
        knobs.set_override("HOROVOD_TRACE_DIR", str(tmp_path))
        spans.enable(buffer_spans=256)
        return spans

    def test_anomaly_fires_flight_recording(self, tmp_path):
        self._tracing(tmp_path)
        mon = numerics.NumericsMonitor(check_every=1, action="warn")
        mon.observe_step(3, loss=float("nan"))
        assert mon.summary()["anomalies"] == 1
        assert mon.summary()["by_kind"] == {"nonfinite": 1}
        flights = list(tmp_path.glob("flight-numerics-nonfinite-*.json"))
        assert flights, list(tmp_path.iterdir())
        payload = json.loads(flights[0].read_text())
        assert payload["metadata"]["reason"].startswith("numerics-")
        names = [e.get("name") for e in payload["traceEvents"]]
        assert "numerics.anomaly" in names

    def test_nonfinite_localized_via_bucket_layout(self, tmp_path):
        self._tracing(tmp_path)
        layout = numerics.bucket_param_map(
            {"a": np.zeros((256,), np.float32),
             "b": np.zeros((256,), np.float32)}, bucket_bytes=1024)
        mon = numerics.NumericsMonitor(bucket_params=layout,
                                       check_every=1, action="warn")
        mon.observe_step(5, nonfinite_counts=np.array([0, 3]))
        a = mon.summary()["last"]
        assert a["kind"] == "nonfinite"
        assert a["buckets"][0]["bucket"] == 1
        assert a["buckets"][0]["nonfinite"] == 3
        assert a["buckets"][0]["params"]

    def test_degrade_action_flips_healthz_and_heals(self, tmp_path):
        from horovod_tpu import metrics as M
        self._tracing(tmp_path)
        mon = numerics.NumericsMonitor(check_every=1, action="degrade")
        mon.observe_step(1, loss=float("inf"))
        h = M.health_snapshot()
        assert h["status"] == "degraded"
        assert "numerics" in h["fault_domain"]["shed"]
        # a clean drain heals the shed site
        mon.observe_step(2, loss=1.0)
        assert M.health_snapshot()["fault_domain"]["shed"] == []

    def test_abort_action_raises(self, tmp_path):
        self._tracing(tmp_path)
        mon = numerics.NumericsMonitor(check_every=1, action="abort")
        with pytest.raises(numerics.NumericsAnomalyError):
            mon.observe_step(1, loss=float("nan"))

    def test_cadence_buffers_until_due(self):
        mon = numerics.NumericsMonitor(check_every=100, action="warn")
        mon.observe_step(1, loss=float("nan"))
        assert mon.summary()["anomalies"] == 0    # buffered
        assert [a["kind"] for a in mon.drain()] == ["nonfinite"]
        assert mon.summary()["anomalies"] == 1

    def test_eager_coordinator_fused_aggregates(self, hvd_ctx):
        knobs.set_override("HOROVOD_NUMERICS", True)
        knobs.set_override("HOROVOD_NUMERICS_CHECK_EVERY", 1)
        x = np.ones((8, 16), np.float32)
        x[2, 5] = np.nan
        h1 = hvd.allreduce_async(x, name="num_bad", op=hvd.Sum)
        h2 = hvd.allreduce_async(np.ones((8, 4), np.float32),
                                 name="num_good", op=hvd.Sum)
        hvd.synchronize(h1)
        hvd.synchronize(h2)
        mon = numerics.get_monitor()
        assert mon is not None
        mon.drain()
        # exactly ONE anomaly for one poisoned bin: the bucket detector
        # names it; the global-norm EWMA must not double-report (bins
        # are not the global gradient)
        assert [a["kind"] for a in mon.anomalies] == ["nonfinite"]
        hit = mon.anomalies[0]
        assert hit["signal"] == "buckets"
        assert any(b.get("label") == "num_bad"
                   for b in hit["buckets"]), hit

    def test_train_loop_observes_loss(self, hvd_ctx):
        import jax.numpy as jnp
        import optax

        from horovod_tpu.parallel import trainer
        knobs.set_override("HOROVOD_NUMERICS", True)
        knobs.set_override("HOROVOD_NUMERICS_CHECK_EVERY", 1)

        def loss_fn(params, batch):
            return jnp.mean((batch @ params["w"]) ** 2)

        init_fn, step, put = trainer.data_parallel_train_step(
            loss_fn, optax.sgd(0.01), hvd.mesh())
        state = init_fn({"w": jnp.ones((4, 1), jnp.float32)})
        batches = [
            (put(np.ones((8, 4), np.float32)),),
            (put(np.full((8, 4), np.nan, np.float32)),),  # poison batch
        ]
        state, info = trainer.train_loop(step, state, batches)
        assert info["final_step"] == 2
        mon = numerics.get_monitor()
        assert mon.summary()["by_kind"].get("nonfinite", 0) >= 1


# ---------------------------------------------------------------------------
# the ledger + regression sentinel
# ---------------------------------------------------------------------------

class TestLedger:
    def test_append_and_read(self, tmp_path):
        _enable_accounting()
        p = str(tmp_path / "ledger.jsonl")
        rec = ledger.append_record(path=p, bench={"value": 1.0})
        assert rec["schema"] == 1
        assert set(rec) >= {"goodput", "numerics", "knob_fingerprint",
                            "collective_fingerprints", "bench", "run_id"}
        assert len(rec["knob_fingerprint"]) == 16
        rows = ledger.read_ledger(p)
        assert len(rows) == 1 and rows[0]["bench"] == {"value": 1.0}

    def test_torn_tail_line_skipped(self, tmp_path):
        p = tmp_path / "ledger.jsonl"
        p.write_text('{"schema": 1, "goodput": {}}\n{"torn')
        assert len(ledger.read_ledger(str(p))) == 1

    def test_shutdown_writes_once(self, tmp_path):
        p = str(tmp_path / "ledger.jsonl")
        knobs.set_override("HOROVOD_GOODPUT_LEDGER", p)
        hvd.init()
        hvd.shutdown()
        assert len(ledger.read_ledger(p)) == 1
        # an explicit append marks the run recorded: the next
        # init/shutdown cycle writes exactly one more record
        hvd.init()
        ledger.append_record(bench={"value": 2.0})
        hvd.shutdown()
        rows = ledger.read_ledger(p)
        assert len(rows) == 2
        assert rows[-1]["bench"] == {"value": 2.0}

    def test_no_path_is_noop(self):
        assert ledger.append_record() is None

    def _bench_dir(self, tmp_path, values):
        for i, v in enumerate(values, start=1):
            (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(
                {"parsed": {"metric": "m", "value": v}}))
        return str(tmp_path)

    def test_regression_report_pass(self, tmp_path):
        d = self._bench_dir(tmp_path, [100.0, 110.0, 108.0])
        r = ledger.regression_report(d, path=str(tmp_path / "none.jsonl"))
        assert r["verdict"] == "pass"
        bench = [c for c in r["checks"]
                 if c["check"] == "bench_throughput"][0]
        assert bench["status"] == "pass"
        assert bench["best_prior"] == 110.0

    def test_malformed_bench_round_skipped(self, tmp_path):
        d = self._bench_dir(tmp_path, [100.0, 101.0])
        (tmp_path / "BENCH_r03.json").write_text(json.dumps(
            {"parsed": {"metric": "m", "value": "n/a"}}))
        r = ledger.regression_report(d)
        assert r["bench_rounds"] == [1, 2]      # bad round dropped
        assert r["verdict"] == "pass"

    def test_regression_report_regress(self, tmp_path):
        d = self._bench_dir(tmp_path, [100.0, 110.0, 80.0])
        r = ledger.regression_report(d)
        assert r["verdict"] == "regress"

    def test_regression_report_numerics_gate(self, tmp_path):
        d = self._bench_dir(tmp_path, [100.0, 101.0])
        p = tmp_path / "ledger.jsonl"
        p.write_text(json.dumps(
            {"schema": 1, "goodput": {"goodput_fraction": 0.5},
             "numerics": {"anomalies": 2,
                          "by_kind": {"nonfinite": 2}}}) + "\n")
        r = ledger.regression_report(d, path=str(p))
        assert r["verdict"] == "regress"
        gate = [c for c in r["checks"] if c["check"] == "numerics_clean"][0]
        assert gate["status"] == "regress" and gate["anomalies"] == 2

    def test_regression_report_goodput_history(self, tmp_path):
        d = self._bench_dir(tmp_path, [100.0, 101.0])
        p = tmp_path / "ledger.jsonl"
        rows = [{"schema": 1, "goodput": {"goodput_fraction": f},
                 "numerics": {"anomalies": 0}} for f in (0.5, 0.52, 0.2)]
        p.write_text("".join(json.dumps(r) + "\n" for r in rows))
        r = ledger.regression_report(d, path=str(p))
        gp = [c for c in r["checks"] if c["check"] == "goodput_fraction"][0]
        assert gp["status"] == "regress"

    def test_regression_report_five_round_history(self, tmp_path):
        """A verdict over a five-round trajectory whose best round is
        not the newest."""
        d = self._bench_dir(tmp_path, [2151.12, 2516.48, 2523.94,
                                       2495.87, 2519.41])
        r = ledger.regression_report(d, path="/nonexistent.jsonl")
        assert r["bench_rounds"] == [1, 2, 3, 4, 5]
        bench = [c for c in r["checks"]
                 if c["check"] == "bench_throughput"][0]
        assert bench["status"] == "pass"
        assert bench["best_prior_round"] == 3
        assert r["verdict"] == "pass"

    def test_regression_report_without_chip_record_skips(self):
        """The repo commits no BENCH round: the throughput axis says so
        instead of judging."""
        r = ledger.regression_report(REPO, path="/nonexistent.jsonl")
        assert r["bench_rounds"] == []
        bench = [c for c in r["checks"]
                 if c["check"] == "bench_throughput"][0]
        assert bench["status"] == "skipped"
        assert "no chip record" in bench["reason"]

    # ---- the serving axis (BENCH_SERVE.json vs serve-bench records) ----

    def _serve_setup(self, tmp_path, cur, priors):
        (tmp_path / "BENCH_SERVE.json").write_text(json.dumps({
            "continuous": {
                "tokens_per_s": cur[0],
                "ttft_ms": {"p50": 1.0, "p99": cur[1]},
                "tpot_ms": {"p50": 1.0, "p99": cur[2]}}}))
        p = tmp_path / "ledger.jsonl"
        rows = [{"schema": 1,
                 "goodput": {"goodput_fraction": 0.5},
                 "numerics": {"anomalies": 0},
                 "bench": {"metric": "serve_continuous_vs_static",
                           "continuous_tokens_per_s": t,
                           "ttft_ms": {"p99": f},
                           "tpot_ms": {"p99": o}}}
                for t, f, o in priors]
        p.write_text("".join(json.dumps(r) + "\n" for r in rows))
        return str(tmp_path), str(p)

    def _serve_check(self, report, name):
        return [c for c in report["checks"] if c["check"] == name][0]

    def test_serve_regression_pass_within_tolerance(self, tmp_path):
        # priors: two serve runs; the newest one IS the committed
        # artifact's run, so only the older one is history
        d, p = self._serve_setup(tmp_path, (980.0, 156.0, 20.9),
                                 [(1000.0, 150.0, 20.0),
                                  (980.0, 156.0, 20.9)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        for name in ("serve_tokens_per_s", "serve_ttft_p99",
                     "serve_tpot_p99"):
            assert self._serve_check(r, name)["status"] == "pass", name
        tps = self._serve_check(r, "serve_tokens_per_s")
        assert tps["best_prior"] == 1000.0 and tps["priors"] == 1

    def test_serve_throughput_floor_regresses(self, tmp_path):
        d, p = self._serve_setup(tmp_path, (900.0, 150.0, 20.0),
                                 [(1000.0, 150.0, 20.0),
                                  (900.0, 150.0, 20.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        assert self._serve_check(
            r, "serve_tokens_per_s")["status"] == "regress"
        assert r["verdict"] == "regress"

    def test_serve_tail_latency_ceiling_regresses(self, tmp_path):
        # throughput up but p99 TPOT blown: still a regression — the
        # serve SLO lives on the tail, not the mean
        d, p = self._serve_setup(tmp_path, (1100.0, 150.0, 30.0),
                                 [(1000.0, 150.0, 20.0),
                                  (1100.0, 150.0, 30.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        assert self._serve_check(
            r, "serve_tokens_per_s")["status"] == "pass"
        assert self._serve_check(
            r, "serve_tpot_p99")["status"] == "regress"
        assert r["verdict"] == "regress"

    def test_serve_axis_skipped_without_history(self, tmp_path):
        # one serve record = the current run itself: nothing to judge
        d, p = self._serve_setup(tmp_path, (980.0, 160.0, 22.0),
                                 [(980.0, 160.0, 22.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        sk = self._serve_check(r, "serve_tokens_per_s")
        assert sk["status"] == "skipped" and "fewer than 2" in sk["reason"]
        # and with no artifact at all
        (tmp_path / "BENCH_SERVE.json").unlink()
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        sk = self._serve_check(r, "serve_ttft_p99")
        assert sk["status"] == "skipped" and "BENCH_SERVE" in sk["reason"]

    def test_serve_axis_against_committed_artifact(self):
        """BENCH_SERVE.json as committed parses into a serving point
        (the sentinel's current side never crashes on the real file)."""
        cur = ledger._serve_current(REPO)
        assert cur is not None
        assert cur["tokens_per_s"] > 0
        assert cur["ttft_p99_ms"] > 0 and cur["tpot_p99_ms"] > 0

    # ---- the fleet axis (BENCH_SERVE.json fleet block vs records) ----

    def _fleet_setup(self, tmp_path, cur, priors):
        (tmp_path / "BENCH_SERVE.json").write_text(json.dumps({
            "continuous": {
                "tokens_per_s": 1000.0,
                "ttft_ms": {"p50": 1.0, "p99": 150.0},
                "tpot_ms": {"p50": 1.0, "p99": 20.0}},
            "fleet": {
                "scaling": [
                    {"replicas": 1, "tokens_per_s": cur[0] / 2},
                    {"replicas": 2, "tokens_per_s": cur[0]}],
                "autoscale": {"ttft_after_grow_ms": cur[1]}}}))
        p = tmp_path / "ledger.jsonl"
        rows = [{"schema": 1,
                 "goodput": {"goodput_fraction": 0.5},
                 "numerics": {"anomalies": 0},
                 "bench": {"metric": "serve_fleet",
                           "fleet_tokens_per_s": t,
                           "ttft_after_grow_ms": g}}
                for t, g in priors]
        p.write_text("".join(json.dumps(r) + "\n" for r in rows))
        return str(tmp_path), str(p)

    def test_fleet_axis_pass_and_peak_replica_row_used(self, tmp_path):
        d, p = self._fleet_setup(tmp_path, (1900.0, 42.0),
                                 [(2000.0, 40.0), (1900.0, 42.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.1)
        tps = self._serve_check(r, "fleet_tokens_per_s")
        assert tps["status"] == "pass"
        # current side reads the largest-replica scaling row, not row 0
        assert tps["current"] == 1900.0 and tps["best_prior"] == 2000.0
        assert self._serve_check(
            r, "fleet_ttft_after_grow")["status"] == "pass"

    def test_fleet_throughput_floor_regresses(self, tmp_path):
        d, p = self._fleet_setup(tmp_path, (1500.0, 40.0),
                                 [(2000.0, 40.0), (1500.0, 40.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        assert self._serve_check(
            r, "fleet_tokens_per_s")["status"] == "regress"
        assert r["verdict"] == "regress"

    def test_fleet_grow_ttft_ceiling_regresses(self, tmp_path):
        # aggregate throughput fine but scale-up responsiveness blown
        d, p = self._fleet_setup(tmp_path, (2100.0, 90.0),
                                 [(2000.0, 40.0), (2100.0, 90.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        assert self._serve_check(
            r, "fleet_tokens_per_s")["status"] == "pass"
        assert self._serve_check(
            r, "fleet_ttft_after_grow")["status"] == "regress"
        assert r["verdict"] == "regress"

    def test_fleet_axis_skipped_without_block_or_history(self, tmp_path):
        d, p = self._fleet_setup(tmp_path, (2000.0, 40.0),
                                 [(2000.0, 40.0)])
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        sk = self._serve_check(r, "fleet_tokens_per_s")
        assert sk["status"] == "skipped" and "fewer than 2" in sk["reason"]
        # serve-only artifact (no fleet block): axis skips, not crashes
        (tmp_path / "BENCH_SERVE.json").write_text(json.dumps({
            "continuous": {"tokens_per_s": 1000.0,
                           "ttft_ms": {"p99": 150.0},
                           "tpot_ms": {"p99": 20.0}}}))
        r = ledger.regression_report(d, path=p, tolerance=0.05)
        sk = self._serve_check(r, "fleet_ttft_after_grow")
        assert sk["status"] == "skipped" and "fleet block" in sk["reason"]


# ---------------------------------------------------------------------------
# end to end: a real train loop's breakdown closes
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_train_loop_phase_breakdown_closes(self, hvd_ctx, tmp_path):
        import jax.numpy as jnp
        import optax

        from horovod_tpu.parallel import trainer

        def loss_fn(params, batch):
            return jnp.mean((batch @ params["w"]) ** 2)

        init_fn, step, put = trainer.data_parallel_train_step(
            loss_fn, optax.sgd(0.01), hvd.mesh())
        state = init_fn({"w": jnp.ones((4, 1), jnp.float32)})
        batches = [(put(np.ones((8, 4), np.float32)),)
                   for _ in range(5)]
        state, info = trainer.train_loop(step, state, batches)
        assert info["final_step"] == 5
        r = hvd.goodput_report()
        assert abs(r["attributed_seconds"] - r["total_seconds"]) \
            <= 0.01 * r["total_seconds"]
        assert r["phases"]["step_compute"] > 0
        assert r["current_phase"] == "idle"
        assert r["goodput_fraction"] > 0
