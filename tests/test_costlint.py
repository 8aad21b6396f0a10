"""Cost-tier resource analysis (hvd.cost_report / hvdlint --cost,
HVD7xx).

The seeded-resource-bug corpus in tests/data/costlint/steps.py must be
flagged by EXACTLY its intended rule, the clean twins must come back
empty, the tile/liveness/restream model must hold on hand-written HLO,
and the CLI must ride the shared baseline/suppression pipeline with the
same exit-code contract as every other tier."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import rules_cost
from horovod_tpu.config import knobs

HERE = os.path.dirname(__file__)
REPO = os.path.abspath(os.path.join(HERE, ".."))
STEPS = os.path.join(HERE, "data", "costlint", "steps.py")


def _load_steps():
    spec = importlib.util.spec_from_file_location("costlint_steps", STEPS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


steps = _load_steps()


def run_target(t):
    fs, report = hvd.cost_report(t.step_fn, t.args, mesh=t.mesh,
                                 name=t.name, **t.options)
    return fs, report


def codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# the tile model, on paper (no compiles)
# ---------------------------------------------------------------------------

class TestTileModel:
    def test_f32_lane_padding_is_the_measured_bn_amplification(self):
        # C=64 -> 128: the statically-reproduced PERF.md r2 BN wall.
        assert rules_cost.padded_dims((65536, 64), "f32") == (65536, 128)
        assert rules_cost.padded_bytes("f32", (65536, 64)) \
            == 2 * rules_cost.shape_bytes("f32", (65536, 64))

    def test_sublane_depends_on_itemsize(self):
        assert rules_cost.sublane("f32") == 8
        assert rules_cost.sublane("bf16") == 16
        assert rules_cost.sublane("s8") == 32
        assert rules_cost.padded_dims((3, 256), "bf16") == (16, 256)

    def test_rank1_pads_lanes_only(self):
        assert rules_cost.padded_dims((100,), "f32") == (128,)

    def test_pathological_lane_pad_models_a_relayout(self):
        # s32[N, 4] would pad 32x; XLA relayouts instead of paying it.
        dims = rules_cost.padded_dims((6422528, 4), "s32")
        assert dims == (rules_cost._round_up(6422528 * 4,
                                             rules_cost.LANE),)

    def test_aligned_shapes_pay_nothing(self):
        assert rules_cost.padded_bytes("f32", (4096, 4096)) \
            == rules_cost.shape_bytes("f32", (4096, 4096))


# ---------------------------------------------------------------------------
# liveness + restream on hand-written scheduled HLO
# ---------------------------------------------------------------------------

_HLO = """\
HloModule synthetic, is_scheduled=true

ENTRY %main (p0: f32[4096,1024], p1: f32[1024,4096]) -> f32[] {
  %p0 = f32[4096,1024]{1,0} parameter(0)
  %p1 = f32[1024,4096]{1,0} parameter(1)
  %c0 = f32[] constant(0)
  %dot.1 = f32[4096,4096]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %reduce.1 = f32[] reduce(%dot.1, %c0), dimensions={0,1}
  %reduce.2 = f32[] reduce(%dot.1, %c0), dimensions={0,1}
  ROOT %reduce.3 = f32[] reduce(%dot.1, %c0), dimensions={0,1}
}
"""


class TestSyntheticHlo:
    def test_parse_finds_the_entry_schedule(self):
        comps, entry = rules_cost.parse_computations(_HLO)
        assert entry == "main"
        assert [i.op for i in comps["main"]] == \
            ["parameter", "parameter", "constant", "dot", "reduce",
             "reduce", "reduce"]
        # operands are bare references, resolved to their producer's shape
        assert comps["main"][3].operands == [
            ("f32", (4096, 1024), "p0"), ("f32", (1024, 4096), "p1")]

    def test_liveness_peak_is_the_dot_result(self):
        comps, entry = rules_cost.parse_computations(_HLO)
        lv = rules_cost.liveness(comps[entry])
        dot_bytes = rules_cost.padded_bytes("f32", (4096, 4096))
        # the dot result dominates; the scalar reduce results ride along
        assert dot_bytes <= lv["peak_bytes"] < dot_bytes + 1024

    def test_restream_counts_distinct_readers(self):
        comps, entry = rules_cost.parse_computations(_HLO)
        rows = rules_cost.restreamed(comps[entry], 1 << 20, 3)
        assert len(rows) == 1
        assert rows[0]["name"] == "dot.1"
        assert rows[0]["reads"] == 3
        # parameters are never restream candidates
        assert rules_cost.restreamed(comps[entry], 0, 1)[0]["op"] == "dot"

    def test_dot_flops_use_contracting_dim(self):
        comps, entry = rules_cost.parse_computations(_HLO)
        dot = comps[entry][3]
        assert rules_cost._dot_flops(dot) == 2 * 4096 * 4096 * 1024


# ---------------------------------------------------------------------------
# seeded bugs -> exactly their intended rule; clean twins -> empty
# ---------------------------------------------------------------------------

class TestSeededFixtures:
    def test_lane_padded_elementwise_is_hvd701(self):
        fs, report = run_target(steps.bad_padding())
        assert codes(fs) == ["HVD701"]
        assert "2.00x" in fs[0].message
        assert report["totals"]["bytes_padded"] \
            > report["totals"]["bytes_logical"]

    def test_budget_bust_is_hvd702(self):
        fs, report = run_target(steps.bad_oom())
        assert codes(fs) == ["HVD702"]
        assert "HBM budget" in fs[0].message
        acc = report["accounting"]
        assert acc["peak_bytes"] > acc["budget_bytes"] == 1 << 30

    def test_multi_pass_intermediate_is_hvd703(self):
        fs, report = run_target(steps.bad_restream())
        assert codes(fs) == ["HVD703"]
        assert "re-read from HBM" in fs[0].message
        assert report["restreamed"][0]["reads"] >= int(
            knobs.get("HOROVOD_COST_RESTREAM_READS"))

    def test_replicated_moments_are_hvd704(self):
        fs, report = run_target(steps.bad_replicated())
        assert codes(fs) == ["HVD704"]
        assert "replicated across the data axis" in fs[0].message
        assert report["accounting"]["sharding_known"]

    def test_stale_rates_are_hvd705(self):
        fs, report = run_target(steps.bad_roofline())
        assert codes(fs) == ["HVD705"]
        assert "SCALING.json" in fs[0].message
        assert report["measured"]["ratio"] > 10

    def test_clean_twins_report_empty(self):
        for t in steps.all_good():
            fs, _ = run_target(t)
            assert fs == [], t.name

    def test_findings_anchor_to_the_step_source(self):
        f, _ = run_target(steps.bad_oom())
        assert f[0].path.endswith("steps.py")
        assert f[0].line > 1
        assert f[0].symbol

    def test_suppression_on_def_line_honored(self):
        fs, report = run_target(steps.suppressed_oom())
        assert fs == []
        assert report.get("suppressed") == ["HVD702"]


# ---------------------------------------------------------------------------
# the report is the COST.json artifact: structure must hold
# ---------------------------------------------------------------------------

class TestReportStructure:
    def test_report_carries_the_accounting_breakdown(self):
        _, report = run_target(steps.good_oom())
        acc = report["accounting"]
        for key in ("params_bytes", "opt_state_bytes", "other_arg_bytes",
                    "transient_peak_bytes", "peak_bytes", "budget_bytes",
                    "top_transients"):
            assert key in acc, key
        assert acc["peak_bytes"] >= acc["transient_peak_bytes"]

    def test_projection_composition_is_declared(self):
        _, report = run_target(steps.good_restream())
        proj = report["projection"]
        assert proj["step_ms_composition"] == \
            "matmul_flops + bn_restream + ring_collectives"
        assert proj["stream_ms_upper_bound"] >= 0
        assert set(proj["classes"]) == {"matmul", "stream", "collective"}

    def test_corrections_are_recorded(self):
        _, report = run_target(steps.good_padding())
        assert report["corrections"]["f32_width_scale"] == 1.0
        assert report["corrections"]["loop_scale"] >= 1.0

    def test_no_measurement_means_no_verdict(self):
        fs, report = run_target(steps.good_oom())
        assert report["measured"] is None
        assert "HVD705" not in codes(fs)

    def test_fingerprint_is_stable_per_executable(self):
        _, a = run_target(steps.good_roofline())
        _, b = run_target(steps.good_roofline())
        assert a["fingerprint"] == b["fingerprint"]


# ---------------------------------------------------------------------------
# CLI integration (hvdlint --cost)
# ---------------------------------------------------------------------------

def run_cli(*argv, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8"
                            ).strip()
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", *argv],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=600)


@pytest.mark.slow
class TestCliCost:
    def test_all_bad_targets_fail_with_their_codes(self):
        out = run_cli("--cost", "tests/data/costlint/steps.py:all_bad",
                      "--no-baseline", "--format", "json")
        assert out.returncode == 1, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        got = sorted({f["code"] for f in payload["findings"]})
        assert got == ["HVD701", "HVD702", "HVD703", "HVD704", "HVD705"]

    def test_all_good_targets_pass(self):
        out = run_cli("--cost", "tests/data/costlint/steps.py:all_good",
                      "--no-baseline")
        assert out.returncode == 0, out.stdout + out.stderr

    def test_cost_findings_flow_through_baseline(self, tmp_path):
        bl = str(tmp_path / "bl.json")
        wrote = run_cli("--cost", "tests/data/costlint/steps.py:bad_oom",
                        "--baseline", bl, "--write-baseline")
        assert wrote.returncode == 0, wrote.stdout + wrote.stderr
        again = run_cli("--cost", "tests/data/costlint/steps.py:bad_oom",
                        "--baseline", bl)
        assert again.returncode == 0, again.stdout + again.stderr
        assert "baselined" in again.stdout

    def test_list_rules_includes_hvd7xx(self):
        out = run_cli("--list-rules")
        assert out.returncode == 0
        for code in ("HVD701", "HVD702", "HVD703", "HVD704", "HVD705"):
            assert code in out.stdout

    def test_crash_in_target_is_usage_exit_2(self):
        out = run_cli("--cost", "tests/data/costlint/steps.py:no_such",
                      "--no-baseline")
        assert out.returncode == 2, out.stdout + out.stderr
