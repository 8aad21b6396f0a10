"""CI pipeline validation (reference analogue: test/single/test_buildkite.py
— the reference validates its generated Buildkite pipeline; here the GitHub
Actions workflow is checked for well-formedness and required jobs)."""

import os

import yaml

CI_PATH = os.path.join(os.path.dirname(__file__), "..",
                       ".github", "workflows", "ci.yml")


def load_ci():
    with open(CI_PATH) as f:
        return yaml.safe_load(f)


def test_ci_workflow_parses_and_has_required_jobs():
    wf = load_ci()
    assert set(wf["jobs"]) >= {"test", "entrypoints", "examples",
                               "hvdlint", "hvdverify", "hvdmodel",
                               "hvdcost", "hvdcompat",
                               "trace-smoke", "chaos-smoke",
                               "chaos-nightly", "store-smoke",
                               "resize-smoke", "serve-smoke"}
    # 'on' parses as the YAML boolean True key.
    triggers = wf.get("on") or wf.get(True)
    assert "pull_request" in triggers and "push" in triggers
    assert "schedule" in triggers     # nightly deep chaos matrix


def test_ci_chaos_jobs_cover_brownout_and_worker_kill():
    """The chaos-smoke job runs the `-k smoke` chaos subset (which
    includes the kv-brownout and data-worker-kill e2es); the nightly
    job runs the deep `-m "chaos and slow"` matrix (30s brownout
    window) plus the deep-budget hvdmodel tier."""
    wf = load_ci()
    smoke = "\n".join(s.get("run", "")
                      for s in wf["jobs"]["chaos-smoke"]["steps"])
    assert "test_chaos_e2e.py" in smoke and "-m chaos" in smoke \
        and "smoke" in smoke
    nightly = wf["jobs"]["chaos-nightly"]
    assert nightly.get("if") and "schedule" in nightly["if"]
    runs = "\n".join(s.get("run", "") for s in nightly["steps"])
    assert "chaos and slow" in runs
    assert "test_modellint.py" in runs and "-m slow" in runs
    # slow integration tests (the 252s spark elastic e2e) moved out of
    # the per-commit shard into the nightly tier
    assert "integration and slow" in runs
    shard = "\n".join(s.get("run", "")
                      for s in wf["jobs"]["integration"]["steps"])
    assert "integration and not slow" in shard
    # the smoke subset actually CONTAINS the two new e2es
    import re
    src = open(os.path.join(os.path.dirname(__file__),
                            "test_chaos_e2e.py")).read()
    names = re.findall(r"^def (test_\w+)", src, re.MULTILINE)
    assert any("smoke" in n and "brownout" in n for n in names)
    assert any("smoke" in n and "worker_kill" in n for n in names)
    assert any("30s" in n for n in names)


def test_ci_test_job_runs_full_suite_over_python_matrix():
    wf = load_ci()
    test = wf["jobs"]["test"]
    pythons = test["strategy"]["matrix"]["python"]
    assert len(pythons) >= 3
    run_steps = [s.get("run", "") for s in test["steps"]]
    # tier-1 runs through the known-failures wrapper over the whole
    # tests/ tree — new failures (and stale manifest entries) fail CI —
    # with --durations so environmental slow tests show in every log
    assert any("check_known_failures.py" in r and "tests/" in r
               and "--durations=25" in r
               for r in run_steps)


def test_ci_trace_smoke_job_asserts_trace_schema():
    """The trace-smoke job is OVERLAP.json's observed-tier CI guarantee:
    it must run bench.py --trace-report on the virtual mesh and assert
    non-empty span counts + per-bucket attribution from TRACE.json."""
    wf = load_ci()
    steps = [s.get("run", "") for s in wf["jobs"]["trace-smoke"]["steps"]]
    assert any("bench.py --trace-report" in r for r in steps)
    schema = "\n".join(steps)
    for needle in ("TRACE.json", "per_bucket", "spans",
                   "observed_overlap_ratio", "OVERLAP.json"):
        assert needle in schema, needle


def test_known_failures_manifest_is_well_formed():
    """Every manifest entry is a node id of an existing test file, and
    the checker's junit round-trip reconstructs ids in the same form."""
    try:
        from tests.check_known_failures import DEFAULT_KNOWN, load_known
    except ImportError:
        from check_known_failures import DEFAULT_KNOWN, load_known
    known = load_known(DEFAULT_KNOWN)      # the file exists; it may be empty
    for nid in known:
        path = nid.split("::", 1)[0]
        assert "::" in nid, nid
        assert os.path.exists(os.path.join(REPO, path)), nid


def test_known_failures_checker_classifies_new_and_stale(tmp_path):
    import textwrap
    try:
        from tests.check_known_failures import parse_junit
        import tests.check_known_failures as ckf
    except ImportError:
        from check_known_failures import parse_junit
        import check_known_failures as ckf
    junit = tmp_path / "r.xml"
    junit.write_text(textwrap.dedent("""\
        <testsuites><testsuite>
        <testcase classname="tests.test_ci_pipeline" name="test_a">
          <failure message="boom"/></testcase>
        <testcase classname="tests.test_ci_pipeline" name="test_b"/>
        <testcase classname="tests.test_ci_pipeline" name="test_c">
          <skipped/></testcase>
        </testsuite></testsuites>
    """))
    failed, passed = parse_junit(str(junit))
    assert failed == ["tests/test_ci_pipeline.py::test_a"]
    assert passed == ["tests/test_ci_pipeline.py::test_b"]
    del ckf


def test_ci_entrypoints_job_compile_checks_multichip():
    wf = load_ci()
    steps = [s.get("run", "") for s in wf["jobs"]["entrypoints"]["steps"]]
    assert any("dryrun_multichip(8)" in r for r in steps)


def test_ci_examples_job_uses_hvdrun_virtual():
    wf = load_ci()
    steps = [s.get("run", "") for s in wf["jobs"]["examples"]["steps"]]
    assert any("hvdrun --virtual" in r for r in steps)


def test_ci_referenced_example_flags_exist():
    """Every example invocation in CI must use flags the example accepts
    (catches drift between ci.yml and examples/)."""
    import re
    import subprocess
    import sys
    wf = load_ci()
    for job in wf["jobs"].values():
        for step in job["steps"]:
            run = step.get("run", "")
            m = re.search(r"python (examples/\S+\.py)([^\n]*)", run)
            if not m:
                continue
            script, tail = m.group(1), m.group(2)
            flags = re.findall(r"(--[\w-]+)", tail)
            repo = os.path.abspath(
                os.path.join(os.path.dirname(__file__), ".."))
            env = dict(os.environ)
            env["PYTHONPATH"] = os.pathsep.join(
                [repo, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
            helptext = subprocess.run(
                [sys.executable, script, "--help"],
                capture_output=True, text=True, timeout=120,
                cwd=repo, env=env,
            ).stdout
            for flag in flags:
                assert flag in helptext, f"{script} lacks {flag}"


def test_ci_integration_job_is_sharded_with_budgets():
    """Tier-3 suite shards across CI jobs with time budgets (ref
    docker-compose.test.yml matrix sharding; VERDICT r3 W8)."""
    wf = load_ci()
    integ = wf["jobs"]["integration"]
    assert integ["timeout-minutes"] <= 60
    shards = integ["strategy"]["matrix"]["shard"]
    assert len(shards) >= 3
    steps = [s.get("run", "") for s in integ["steps"]]
    assert any("list_integration_shard.py" in r for r in steps)
    # fast tier excludes integration (and the chaos fault-injection
    # tier, which has its own smoke job) so the python-matrix job stays
    # within budget
    test_steps = [s.get("run", "") for s in wf["jobs"]["test"]["steps"]]
    assert any("not integration" in r and "-m" in r for r in test_steps)
    assert any("not chaos" in r for r in test_steps)


def test_ci_hvdlint_job_self_applies_against_baseline():
    """The static analyzer gates the build: the hvdlint job runs the
    self-application (framework + examples + test worker scripts)
    against the checked-in baseline, so any NEW finding fails CI while
    grandfathered ones stay visible in .hvdlint-baseline.json."""
    wf = load_ci()
    job = wf["jobs"]["hvdlint"]
    assert job["timeout-minutes"] <= 15
    steps = [s.get("run", "") for s in job["steps"]]
    run = next(r for r in steps if "horovod_tpu.analysis" in r)
    for target in ("horovod_tpu", "examples", "tests/data"):
        assert target in run
    assert ".hvdlint-baseline.json" in run
    # findings render inline on PRs as workflow annotations
    assert "--format github" in run
    # stale '# hvdlint: disable=' comments fail the job (HVD002)
    assert "--report-unused-suppressions" in run
    # the baseline the job pins must exist in the repo
    assert os.path.exists(os.path.join(
        os.path.dirname(CI_PATH), "..", "..", ".hvdlint-baseline.json"))


def test_ci_hvdmodel_job_checks_protocols_and_corpus():
    """The protocol model checker gates the build: the real protocols
    explore with zero findings within a PR-sized budget, the seeded-bug
    corpus fails with exit EXACTLY 1 (a crash must not read as green),
    the clean twins pass, and every emitted counterexample trace
    replays deterministically."""
    wf = load_ci()
    job = wf["jobs"]["hvdmodel"]
    assert job["timeout-minutes"] <= 20
    steps = [s.get("run", "") for s in job["steps"]]
    real = next(r for r in steps if "--model all" in r)
    assert "JAX_PLATFORMS=cpu" in real and "--model-budget" in real
    corpus = next(r for r in steps if "all_bad" in r)
    assert 'if [ "$rc" != "1" ]' in corpus and "all_clean" in corpus
    replay = next(r for r in steps if "--replay" in r)
    assert ".hvdmodel" in replay


def test_ci_hvdverify_job_verifies_flagship_steps_and_fixtures():
    """The IR verifier gates the build: bench.py --verify-report must
    run the flagship transformer + ResNet DP steps on the virtual CPU
    mesh (failing on any non-baselined HVD5xx finding), and the
    seeded-bug corpus must demonstrably FAIL verification (the verifier
    verifying itself)."""
    wf = load_ci()
    job = wf["jobs"]["hvdverify"]
    assert job["timeout-minutes"] <= 20
    steps = [s.get("run", "") for s in job["steps"]]
    report = next(r for r in steps if "--verify-report" in r)
    assert "JAX_PLATFORMS=cpu" in report
    fixtures = next(r for r in steps if "--ir" in r)
    assert "all_good" in fixtures and "all_bad" in fixtures


def test_ci_hvdcost_job_gates_cost_report_and_corpus():
    """The resource tier gates the build three ways: bench.py
    --cost-report must exit 0 on the builtin steps (BN-wall
    reproduction + OOM verdict gates inside), the COST.json schema the
    regression sentinel reads is asserted in-job, and the
    seeded-resource-bug corpus must demonstrably FAIL analysis with
    exit exactly 1 (the analyzer analyzing itself)."""
    wf = load_ci()
    job = wf["jobs"]["hvdcost"]
    assert job["timeout-minutes"] <= 20
    steps = [s.get("run", "") for s in job["steps"]]
    report = next(r for r in steps if "--cost-report" in r)
    assert "JAX_PLATFORMS=cpu" in report
    schema = next(r for r in steps if "COST.json" in r)
    for key in ("bn_phase", "HVD702", "expected_findings",
                "remeasure_commands"):
        assert key in schema, key
    fixtures = next(r for r in steps if "--cost" in r and "all_bad" in r)
    assert "all_good" in fixtures
    assert '"$rc" != "1"' in fixtures       # exit EXACTLY 1, not a crash


def test_ci_hvdcompat_job_gates_compat_report_and_corpus():
    """The certification tier gates the build three ways: bench.py
    --compat-report must exit 0 on the seeded handoffs (the flagship
    certifies `compatible` with all five rules evaluated; each seeded
    defect earns exactly its rule), the COMPAT.json schema the
    regression sentinel reads is asserted in-job, and the seeded
    handoff-defect corpus must demonstrably FAIL certification with
    exit exactly 1 (the certifier certifying itself)."""
    wf = load_ci()
    job = wf["jobs"]["hvdcompat"]
    assert job["timeout-minutes"] <= 20
    steps = [s.get("run", "") for s in job["steps"]]
    report = next(r for r in steps if "--compat-report" in r)
    assert "JAX_PLATFORMS=cpu" in report
    schema = next(r for r in steps if "COMPAT.json" in r)
    for key in ("verdict", "evaluated", "HVD801", "HVD802", "HVD803",
                "expected_findings", "remeasure_commands"):
        assert key in schema, key
    fixtures = next(r for r in steps
                    if "--compat" in r and "all_bad" in r)
    assert "all_good" in fixtures
    assert '"$rc" != "1"' in fixtures       # exit EXACTLY 1, not a crash


def test_ci_hvdverify_job_asserts_tiered_variant_and_tier_smoke():
    """The DCN two-level tier is CI-locked two ways: the hvdverify job
    asserts the tiered flagship workload's VERIFY.json fingerprints
    (per-tier manifest present, zero wide cross-DCN gradient
    collectives under declared compression), and a tier-smoke step runs
    the virtual-slice flat-vs-two-level A/B through
    `bench.py --overlap-report` (numerical equivalence + ICI/DCN model
    scores — docs/hierarchical.md)."""
    wf = load_ci()
    job = wf["jobs"]["hvdverify"]
    steps = [s.get("run", "") for s in job["steps"]]
    tiered = next(r for r in steps if "transformer_tiered" in r)
    for want in ("tier_gates", "wide_gradient_allreduces",
                 "non_wire_cross_dcn_reductions", "reduce-scatter",
                 "all-gather", "cross_wire_dtype", "fingerprint"):
        assert want in tiered, want
    smoke = next(r for r in steps
                 if "HOROVOD_DCN_VIRTUAL_SLICES" in r)
    assert "--overlap-report" in smoke
    for want in ("dcn_tier_ab", "max_param_delta_flat_vs_two_level",
                 "model_scores", "remeasure_commands"):
        assert want in smoke, want


def test_ci_store_smoke_job_runs_ab_twice_and_gates_warm_path():
    """The artifact-store smoke job runs the cold-vs-warm A/B twice
    (gated after EACH run, so a lucky first report cannot pass alone)
    and pins the warm-path acceptance: ZERO ExecutableCache builder
    invocations, a store-served train step, a restored checkpoint, and
    a ~0 goodput `compile` phase — plus the committed BENCH_TTFS.json
    artifact and the store unit suite."""
    wf = load_ci()
    job = wf["jobs"]["store-smoke"]
    assert job["timeout-minutes"] <= 30
    steps = [s.get("run", "") for s in job["steps"]]
    ab = next(r for r in steps if "--store-report" in r)
    assert "for round in 1 2" in ab \
        and "python bench.py --store-report" in ab
    assert "BENCH_TTFS.json" in ab
    for want in ('warm["cache"]["builds"] == 0',
                 'warm["cache"]["store_hits"] >= 1',
                 'warm["store_step"] == "hit"',
                 'warm["restored"] is True',
                 'warm["goodput_phases"]["compile"]'):
        assert want in ab, want
    assert any("test_artifact_store.py" in r for r in steps)


def test_ci_serve_smoke_job_gates_bench_and_warm_boot():
    """The serving acceptance is CI-locked: the serve-smoke job runs
    `bench.py serve` on the virtual mesh, asserts the BENCH_SERVE.json
    schema (completed requests, p50<=p99 ordering, occupancy in (0,1],
    continuous strictly beating the static baseline, the hvdspec
    prefix/acceptance sweeps bitwise-clean with a >1x uplift at full
    sharing), pins the warm-boot `builds == 0` gate over the spec/COW
    executables, and runs the serving test tier."""
    wf = load_ci()
    job = wf["jobs"]["serve-smoke"]
    assert job["timeout-minutes"] <= 30
    steps = [s.get("run", "") for s in job["steps"]]
    bench = next(r for r in steps if "bench.py serve" in r)
    assert "BENCH_SERVE.json" in bench
    for want in ('cont["completed"] > 0',
                 'cont["ttft_ms"]["p50"] <= cont["ttft_ms"]["p99"]',
                 'cont["tpot_ms"]["p50"] <= cont["tpot_ms"]["p99"]',
                 '0 < cont["batch_occupancy"] <= 1',
                 'd["static_baseline"]["tokens_per_s"]',
                 'd["warm_boot"]["builds"] == 0',
                 '[0.0, 0.5, 1.0]',
                 'r["bitwise_equal_baseline"] for r in psweep',
                 'psweep[-1]["uplift"] > 1.0',
                 '{"ngram:2", "ngram:3", "truncate:1"}',
                 '0 <= r["acceptance_rate"] <= 1',
                 '"serve_cow_copy", "serve_verify_k4", "serve_draft_l1"'):
        assert want in bench, want
    assert any("test_serving.py" in r for r in steps)
    # the committed artifact itself satisfies the same schema
    path = os.path.join(REPO, "BENCH_SERVE.json")
    assert os.path.exists(path), "BENCH_SERVE.json not committed"
    import json
    d = json.load(open(path))
    assert d["gates"]["errors"] == []
    assert d["continuous"]["completed"] > 0
    assert 0 < d["continuous"]["batch_occupancy"] <= 1
    assert d["continuous"]["tokens_per_s"] > \
        d["static_baseline"]["tokens_per_s"]
    assert d["warm_boot"]["builds"] == 0
    psweep = d["prefix_sweep"]
    assert [r["shared_fraction"] for r in psweep] == [0.0, 0.5, 1.0]
    assert all(r["bitwise_equal_baseline"] for r in psweep)
    assert psweep[-1]["prefix_hit_rate"] > psweep[0]["prefix_hit_rate"]
    assert psweep[-1]["uplift"] > 1.0
    asweep = d["acceptance_sweep"]
    assert {r["draft"] for r in asweep} == {"ngram:2", "ngram:3",
                                            "truncate:1"}
    assert all(r["bitwise_equal_baseline"] for r in asweep)
    assert {"serve_cow_copy", "serve_verify_k4", "serve_draft_l1"} <= \
        set(d["warm_boot"]["store_outcomes"])
    assert any("JAX_PLATFORMS=tpu" in c
               for c in d["remeasure_commands"])
    assert any("HOROVOD_SERVE_PREFIX_CACHE" in c and
               "HOROVOD_SERVE_DRAFT" in c
               for c in d["remeasure_commands"])


def test_ci_serve_smoke_job_gates_fleet_phase():
    """The hvdfleet acceptance is CI-locked: serve-smoke runs `bench.py
    serve --fleet` and asserts the fleet block — scaling rows at 1/2/4
    replicas with warm (builds==0) replicas, fleet-of-1 bitwise, the
    autoscaler growing within one scheduling cycle, and the chaos
    replica_kill drill with zero drops and deterministic re-admission.
    The fleet chaos drills also ride the chaos-smoke subset."""
    wf = load_ci()
    job = wf["jobs"]["serve-smoke"]
    steps = [s.get("run", "") for s in job["steps"]]
    bench = next(r for r in steps if "bench.py serve" in r)
    assert "bench.py serve --fleet" in bench
    for want in ('sorted(rows) == [1, 2, 4]',
                 'r["replica_builds"].values()',
                 'fleet["fleet_of_1_bitwise"] is True',
                 'fleet["speedup_at_2"] >= 1.6 or fleet["bottleneck"]',
                 'auto["grow_reaction_cycles"] <= 1',
                 'auto["warm_replica_builds"] == 0',
                 'ch["dropped"] == 0 and ch["readmissions"] >= 1',
                 'ch["deterministic_readmission"] is True'):
        assert want in bench, want
    assert any("test_fleet.py" in r for r in steps)
    # the committed artifact carries the same fleet schema
    import json
    d = json.load(open(os.path.join(REPO, "BENCH_SERVE.json")))
    fleet = d["fleet"]
    rows = {r["replicas"]: r for r in fleet["scaling"]}
    assert sorted(rows) == [1, 2, 4]
    assert all(b == 0 for r in rows.values()
               for b in r["replica_builds"].values())
    assert fleet["fleet_of_1_bitwise"] is True
    assert fleet["speedup_at_2"] >= 1.6 or fleet["bottleneck"]
    assert fleet["autoscale"]["grow_reaction_cycles"] <= 1
    assert fleet["autoscale"]["warm_replica_builds"] == 0
    assert fleet["autoscale"]["ttft_after_grow_ms"] is not None
    assert fleet["chaos"]["dropped"] == 0
    assert fleet["chaos"]["readmissions"] >= 1
    assert fleet["chaos"]["deterministic_readmission"] is True
    assert any("--fleet" in c for c in fleet["remeasure_commands"])
    assert any("JAX_PLATFORMS=tpu" in c
               for c in fleet["remeasure_commands"])


def test_ci_resize_smoke_job_runs_drill_and_model_scenario():
    """The live-resize acceptance is CI-locked: the resize-smoke job
    runs the shrink drill (bitwise cold-start parity + compile-free
    grow-back) at PR budget, model-checks the builtin `resize` scenario
    to zero findings, and proves the seeded twin (plan committed before
    its snapshot) fails with exit EXACTLY 1 while the clean twin
    passes; the full slice-loss drill rides chaos-nightly."""
    wf = load_ci()
    job = wf["jobs"]["resize-smoke"]
    assert job["timeout-minutes"] <= 20
    steps = [s.get("run", "") for s in job["steps"]]
    drill = next(r for r in steps if "test_resize.py" in r)
    assert "-m chaos" in drill and "smoke" in drill
    scenario = next(r for r in steps if "--model resize" in r)
    assert "JAX_PLATFORMS=cpu" in scenario and "--model-budget" in scenario
    twin = next(r for r in steps if "bad_resize_plan_order" in r)
    assert 'if [ "$rc" != "1" ]' in twin
    assert "clean_resize_plan_order" in twin
    # nightly: the deep slice-loss drill
    nightly = "\n".join(s.get("run", "")
                        for s in wf["jobs"]["chaos-nightly"]["steps"])
    assert "test_resize.py" in nightly and "chaos and slow" in nightly
    # the smoke/deep drills actually exist with the promised names
    import re
    src = open(os.path.join(os.path.dirname(__file__),
                            "test_resize.py")).read()
    names = re.findall(r"^def (test_\w+)", src, re.MULTILINE)
    assert any("smoke" in n and "resize" in n and "growback" in n
               for n in names)
    assert any("slice_loss" in n for n in names)


def test_ci_chaos_smoke_job_runs_marked_subset():
    """The chaos harness has a dedicated smoke job: the `-m chaos`
    tier's test_smoke_* subset proves preemption/recovery end-to-end on
    every push without the full kill-9+cooldown e2e cost."""
    wf = load_ci()
    chaos = wf["jobs"]["chaos-smoke"]
    assert chaos["timeout-minutes"] <= 30
    steps = [s.get("run", "") for s in chaos["steps"]]
    assert any("-m chaos" in r and "smoke" in r for r in steps)


def test_integration_shards_cover_all_marked_files():
    import subprocess
    import sys
    shards = load_ci()["jobs"]["integration"]["strategy"]["matrix"]["shard"]
    n = len(shards)          # exercise the split CI actually runs
    got = set()
    for k in shards:
        out = subprocess.run(
            [sys.executable, "tests/list_integration_shard.py",
             str(k), str(n)],
            capture_output=True, text=True,
            cwd=os.path.join(os.path.dirname(__file__), ".."))
        assert out.returncode == 0, out.stderr
        got.update(out.stdout.split())
    try:            # bare `pytest` puts tests/ (not the root) on sys.path
        from tests.list_integration_shard import integration_files
    except ImportError:
        from list_integration_shard import integration_files
    assert got == set(integration_files(os.path.dirname(__file__)))


REPO = os.path.join(os.path.dirname(__file__), "..")


def test_deployment_artifacts_exist_and_are_wired():
    """Deployment artifacts (ref Dockerfile.test.*, docker/helm/): the TPU
    worker image, the CPU test image, and the GKE JobSet manifest exist;
    CI builds them; every path a Dockerfile COPYs exists in the repo."""
    wf = load_ci()
    assert "docker" in wf["jobs"]
    steps = " ".join(str(s.get("run", ""))
                     for s in wf["jobs"]["docker"]["steps"])
    assert "docker/Dockerfile.tpu" in steps
    assert "docker/Dockerfile.test.cpu" in steps
    assert "docker/gke-jobset.yaml" in steps

    for df in ("Dockerfile.tpu", "Dockerfile.test.cpu"):
        path = os.path.join(REPO, "docker", df)
        assert os.path.exists(path), df
        for line in open(path):
            if line.startswith("COPY "):
                for src in line.split()[1:-1]:
                    assert os.path.exists(os.path.join(REPO, src)), \
                        f"{df} COPYs missing path {src}"

    docs = list(yaml.safe_load_all(
        open(os.path.join(REPO, "docker", "gke-jobset.yaml"))))
    jobset, svc = docs
    assert jobset["kind"] == "JobSet" and svc["kind"] == "Service"
    tmpl = (jobset["spec"]["replicatedJobs"][0]["template"]["spec"]
            ["template"]["spec"])
    container = tmpl["containers"][0]
    env = {e["name"] for e in container["env"]}
    # the manifest must wire exactly what `hvdrun --tpu` resolves
    assert {"TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES"} <= env
    assert "google.com/tpu" in container["resources"]["limits"]
