"""Launcher unit tests (reference analogue: test/single/test_run.py —
horovodrun arg parsing, host parsing, command construction with mocked exec)."""

import os
import subprocess
import sys
from unittest import mock

import pytest

from horovod_tpu.runner import launch


def test_parse_hosts_inline():
    assert launch.parse_hosts("h1:4,h2:2", None) == [("h1", 4), ("h2", 2)]
    assert launch.parse_hosts("solo", None) == [("solo", 1)]


def test_parse_hosts_file(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("# comment\nh1 slots=4\nh2:8\n")
    assert launch.parse_hosts(None, str(f)) == [("h1", 4), ("h2", 8)]


def test_env_from_args_knob_mirroring():
    args = launch.build_parser().parse_args(
        ["--fusion-threshold-mb", "64", "--cycle-time-ms", "5",
         "--torus-allreduce", "--autotune", "--timeline-filename", "/tmp/t.json",
         "--mesh-shape", "4,2", "--", "python", "x.py"])
    env = launch.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(64 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "5.0"
    assert env["HOROVOD_TORUS_ALLREDUCE"] == "1"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert env["HOROVOD_TPU_MESH_SHAPE"] == "4,2"


def test_local_launch_virtual_sets_device_count():
    with mock.patch.object(subprocess, "call", return_value=0) as call:
        rc = launch.main(["-np", "4", "--virtual", "--",
                          "python", "-c", "pass"])
    assert rc == 0
    env = call.call_args.kwargs["env"]
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"


def test_local_launch_no_command_errors():
    assert launch.main(["-np", "2"]) == 2


def test_multihost_builds_ssh_commands():
    with mock.patch.object(subprocess, "Popen") as popen:
        popen.return_value.wait.return_value = 0
        rc = launch.main(["-H", "h1:4,h2:4", "--coordinator-port", "1234",
                          "--disable-connectivity-probe",
                          "--", "python", "train.py"])
    assert rc == 0
    assert popen.call_count == 2
    cmd0 = popen.call_args_list[0].args[0]
    assert cmd0[0] == "ssh" and cmd0[1] == "h1"
    remote0 = cmd0[2]
    assert "HVD_TPU_COORDINATOR=h1:1234" in remote0
    assert "HVD_TPU_NUM_PROCESSES=2" in remote0
    assert "HVD_TPU_PROCESS_ID=0" in remote0
    remote1 = popen.call_args_list[1].args[0][2]
    assert "HVD_TPU_PROCESS_ID=1" in remote1


def test_cli_entry_point_runs():
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "--version"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH":
             os.pathsep.join([os.path.dirname(os.path.dirname(
                 os.path.dirname(os.path.abspath(__file__)))) or ".",
                 os.environ.get("PYTHONPATH", "")])})
    assert out.returncode == 0


def test_config_file_sets_defaults_cli_wins(tmp_path):
    """YAML config maps to args; explicit CLI flags beat file values
    (reference config_parser.py override_args contract)."""
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(
        "params:\n"
        "  fusion_threshold_mb: 64\n"
        "  cycle_time_ms: 3.5\n"
        "  cache_capacity: 2048\n"
        "  torus_allreduce: true\n"
        "autotune:\n"
        "  enabled: true\n"
        "  log_file: at.csv\n"
        "timeline:\n"
        "  filename: tl.json\n"
        "  mark_cycles: true\n"
        "stall_check:\n"
        "  enabled: false\n"
        "logging:\n"
        "  level: DEBUG\n"
        "mesh_shape: '4,2'\n")
    argv = ["--config-file", str(cfg), "--cycle-time-ms", "9",
            "--", "python", "x.py"]
    parser = launch.build_parser()
    args = parser.parse_args(argv)
    from horovod_tpu.runner.config_file import (
        cli_overrides, load_config_file, set_args_from_config)
    set_args_from_config(parser, args, load_config_file(str(cfg)),
                         cli_overrides(parser, argv, args.command))
    env = launch.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(64 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "9.0"          # CLI wins
    assert env["HOROVOD_CACHE_CAPACITY"] == "2048"
    assert env["HOROVOD_TORUS_ALLREDUCE"] == "1"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_AUTOTUNE_LOG"] == "at.csv"
    assert env["HOROVOD_TIMELINE"] == "tl.json"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert env["HOROVOD_LOG_LEVEL"] == "DEBUG"
    assert env["HOROVOD_TPU_MESH_SHAPE"] == "4,2"


def test_config_file_elastic_section(tmp_path):
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(
        "elastic:\n"
        "  min_np: 2\n"
        "  max_np: 8\n"
        "  slots: 4\n"
        "  reset_limit: 3\n"
        "  host_discovery_script: ./discover.sh\n")
    parser = launch.build_parser()
    argv = ["--config-file", str(cfg), "--", "python", "x.py"]
    args = parser.parse_args(argv)
    from horovod_tpu.runner.config_file import (
        cli_overrides, load_config_file, set_args_from_config)
    set_args_from_config(parser, args, load_config_file(str(cfg)),
                         cli_overrides(parser, argv, args.command))
    assert args.min_np == 2
    assert args.max_np == 8
    assert args.slots == 4
    assert args.reset_limit == 3
    assert args.host_discovery_script == "./discover.sh"


def test_config_file_rejects_non_mapping(tmp_path):
    cfg = tmp_path / "bad.yaml"
    cfg.write_text("- just\n- a list\n")
    from horovod_tpu.runner.config_file import load_config_file
    with pytest.raises(ValueError):
        load_config_file(str(cfg))


def test_config_file_program_flags_are_not_overrides(tmp_path):
    """Flags of the launched program (no '--' separator) must not mask
    config-file values."""
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text("logging:\n  level: DEBUG\n")
    parser = launch.build_parser()
    argv = ["--config-file", str(cfg), "python", "x.py",
            "--log-level", "INFO"]
    args = parser.parse_args(argv)
    from horovod_tpu.runner.config_file import (
        cli_overrides, load_config_file, set_args_from_config)
    set_args_from_config(parser, args, load_config_file(str(cfg)),
                         cli_overrides(parser, argv, args.command))
    assert args.log_level == "DEBUG"
    assert args.command == ["python", "x.py", "--log-level", "INFO"]


def test_config_file_coerces_string_numbers(tmp_path):
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(
        "params:\n  fusion_threshold_mb: '64'\n"
        "elastic:\n  min_np: '2'\n")
    parser = launch.build_parser()
    argv = ["--config-file", str(cfg), "--", "python", "x.py"]
    args = parser.parse_args(argv)
    from horovod_tpu.runner.config_file import (
        cli_overrides, load_config_file, set_args_from_config)
    set_args_from_config(parser, args, load_config_file(str(cfg)),
                         cli_overrides(parser, argv, args.command))
    assert args.fusion_threshold_mb == 64.0
    assert args.min_np == 2


def test_config_file_rejects_scalar_section(tmp_path):
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    with pytest.raises(ValueError, match="must be a mapping"):
        set_args_from_config(parser, args, {"params": "oops"}, set())
    with pytest.raises(ValueError, match="must be a mapping"):
        set_args_from_config(parser, args, {"stall_check": True}, set())


def test_config_file_rejects_non_bool_for_flag(tmp_path):
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    with pytest.raises(ValueError, match="expected a boolean"):
        set_args_from_config(
            parser, args, {"params": {"torus_allreduce": "yes"}}, set())


def test_elastic_grace_seconds_flag_mirrors_env():
    args = launch.build_parser().parse_args(
        ["--elastic-grace-seconds", "10", "--", "python", "x.py"])
    env = launch.env_from_args(args)
    assert env["HOROVOD_ELASTIC_GRACE_SECONDS"] == "10.0"


def test_config_file_short_option_attached_value_is_override(tmp_path):
    """-Hvalue must count as an explicit CLI override."""
    cfg = tmp_path / "hvd.yaml"
    cfg.write_text("hosts: other:8\nnum_proc: 16\n")
    parser = launch.build_parser()
    argv = ["--config-file", str(cfg), "-Hlocalhost:4", "-np=4",
            "--", "python", "x.py"]
    args = parser.parse_args(argv)
    from horovod_tpu.runner.config_file import (
        cli_overrides, load_config_file, set_args_from_config)
    set_args_from_config(parser, args, load_config_file(str(cfg)),
                         cli_overrides(parser, argv, args.command))
    assert args.hosts == "localhost:4"
    assert args.num_proc == 4


def test_config_file_untyped_scalars_become_strings():
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    set_args_from_config(parser, args,
                         {"logging": {"level": 10}, "mesh_shape": 4}, set())
    assert args.log_level == "10"
    assert args.mesh_shape == "4"
    env = launch.env_from_args(args)
    assert all(isinstance(v, str) for v in env.values())


def test_config_file_rejects_bool_for_numeric_knob():
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    with pytest.raises(ValueError, match="got a boolean"):
        set_args_from_config(parser, args,
                             {"params": {"cache_capacity": True}}, set())


def test_config_file_null_stall_enabled_is_noop_and_nonbool_rejected():
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    set_args_from_config(parser, args, {"stall_check": {"enabled": None}},
                         set())
    assert args.stall_check_disable is False
    with pytest.raises(ValueError, match="stall_check.enabled"):
        set_args_from_config(parser, args, {"stall_check": {"enabled": 1}},
                             set())


def test_config_file_rejects_unknown_keys():
    from horovod_tpu.runner.config_file import set_args_from_config
    parser = launch.build_parser()
    args = parser.parse_args(["--", "python", "x.py"])
    with pytest.raises(ValueError, match="unknown key"):
        set_args_from_config(parser, args,
                             {"params": {"fusion_threshold": 64}}, set())
    with pytest.raises(ValueError, match="unknown key"):
        set_args_from_config(parser, args, {"elastics": {}}, set())


# ---------------------------------------------------------------------------
# pre-launch connectivity probe (ref HorovodRunDriverService NIC discovery,
# runner/driver/driver_service.py:30,162,218)
# ---------------------------------------------------------------------------

def test_probe_learns_worker_addresses():
    """Two 'hosts' (local probe processes, the localhost-alias model):
    the driver learns each one's routable address with no env prep."""
    from horovod_tpu.runner.probe import probe_hosts
    got = probe_hosts(["hostA", "hostB"], local=True, timeout=30)
    assert set(got) == {0, 1}
    for addr in got.values():
        # the interface the worker reached the driver through
        assert addr.count(".") == 3 or addr == "localhost"


def test_probe_fails_fast_on_unreachable_host():
    from horovod_tpu.runner.probe import probe_hosts

    def argv_fn(host, client_argv):
        if host == "bad":
            return ["python3", "-c", "import sys; sys.exit('no route')"]
        from horovod_tpu.runner.probe import _default_argv_fn
        return _default_argv_fn(None, True)(host, client_argv)

    with pytest.raises(RuntimeError, match="bad"):
        probe_hosts(["good", "bad"], local=True, timeout=20,
                    argv_fn=argv_fn)


def test_multihost_launch_sets_advertise_host():
    """The probed address rides into each host's env as
    HVD_TPU_ADVERTISE_HOST (consumed by the data-service registry)."""
    from horovod_tpu.runner import probe as probe_mod
    with mock.patch.object(probe_mod, "probe_hosts",
                           return_value={0: "10.0.0.5", 1: "10.0.0.6"}), \
         mock.patch.object(subprocess, "Popen") as popen:
        popen.return_value.wait.return_value = 0
        rc = launch.main(["-H", "h1:4,h2:4", "--",
                          "python", "train.py"])
    assert rc == 0
    remote0 = popen.call_args_list[0].args[0][2]
    remote1 = popen.call_args_list[1].args[0][2]
    assert "HVD_TPU_ADVERTISE_HOST=10.0.0.5" in remote0
    assert "HVD_TPU_ADVERTISE_HOST=10.0.0.6" in remote1


def test_probe_rejects_spoofed_reports():
    """Unauthenticated reports must not place an advertise address or fake
    a host's liveness (the reference's task services authenticate with the
    launcher secret, runner/common/util/secret.py)."""
    import json as _json
    import socket as _socket
    from horovod_tpu.runner.probe import ProbeServer
    server = ProbeServer(expected=1, secret=b"real-secret")
    try:
        # Attacker without the secret tries to claim index 0.
        body = _json.dumps({"index": 0, "local_ip": "6.6.6.6",
                            "hostname": "evil"}, sort_keys=True)
        s = _socket.create_connection(("127.0.0.1", server.port), timeout=5)
        s.sendall((_json.dumps({"body": body, "mac": "00" * 32})
                   + "\n").encode())
        s.close()
        assert not server.wait(0.5)
        assert server.results == {}
    finally:
        server.close()


# ---------------------------------------------------------------------------
# TPU-pod launch (runner/tpu_pod.py — the scheduler-launch role of
# reference js_run.py:1-130 / util/lsf.py for the TPU deployment path)
# ---------------------------------------------------------------------------

def _tpu_args(extra=()):
    from horovod_tpu.runner.launch import build_parser
    return build_parser().parse_args(
        ["--tpu", *extra, "--", "python", "train.py"])


def test_resolve_tpu_pod_from_env():
    from horovod_tpu.runner.tpu_pod import resolve_tpu_pod
    info = resolve_tpu_pod(
        env={"TPU_WORKER_HOSTNAMES": "w0,w1,w2,w3", "TPU_WORKER_ID": "2"},
        fetch=lambda attr: None)
    assert info.hostnames == ["w0", "w1", "w2", "w3"]
    assert info.worker_id == 2 and info.source == "env"


def test_resolve_tpu_pod_from_metadata():
    from horovod_tpu.runner.tpu_pod import resolve_tpu_pod
    meta = {"worker-network-endpoints":
            "uid0:8476:10.0.0.1,uid1:8476:10.0.0.2",
            "agent-worker-number": "1"}
    info = resolve_tpu_pod(env={}, fetch=meta.get)
    assert info.hostnames == ["10.0.0.1", "10.0.0.2"]
    assert info.worker_id == 1 and info.source == "metadata"


def test_resolve_tpu_pod_absent():
    from horovod_tpu.runner.tpu_pod import resolve_tpu_pod
    assert resolve_tpu_pod(env={}, fetch=lambda attr: None) is None


def test_tpu_on_worker_mode_wires_rendezvous_env(monkeypatch):
    from horovod_tpu.runner import tpu_pod
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "wa,wb,wc")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    with mock.patch.object(subprocess, "call", return_value=0) as call:
        rc = tpu_pod.launch_tpu(_tpu_args(), {"HOROVOD_AUTOTUNE": "1"})
    assert rc == 0
    cmd = call.call_args[0][0]
    env = call.call_args[1]["env"]
    assert cmd == ["python", "train.py"]
    assert env["HVD_TPU_COORDINATOR"] == "wa:9733"
    assert env["HVD_TPU_NUM_PROCESSES"] == "3"
    assert env["HVD_TPU_PROCESS_ID"] == "1"
    assert env["HOROVOD_AUTOTUNE"] == "1"


def test_tpu_driver_mode_falls_back_to_ssh(monkeypatch):
    from horovod_tpu.runner import tpu_pod
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    monkeypatch.setattr(tpu_pod, "resolve_tpu_pod",
                        lambda: tpu_pod.TpuPodInfo(["w0", "w1"], None,
                                                   "metadata"))
    args = _tpu_args(["--disable-connectivity-probe"])
    with mock.patch.object(subprocess, "Popen") as popen:
        popen.return_value.wait.return_value = 0
        popen.return_value.stdin = mock.MagicMock()
        rc = tpu_pod.launch_tpu(args, {})
    assert rc == 0
    assert popen.call_count == 2
    first = popen.call_args_list[0][0][0]
    assert first[0] == "ssh" and "w0" in first
    remote = first[-1]
    assert "HVD_TPU_PROCESS_ID=0" in remote
    assert "HVD_TPU_NUM_PROCESSES=2" in remote
    assert "HVD_TPU_COORDINATOR=w0:9733" in remote


def test_tpu_no_metadata_no_hosts_errors(monkeypatch, capsys):
    from horovod_tpu.runner import tpu_pod
    monkeypatch.setattr(tpu_pod, "resolve_tpu_pod", lambda: None)
    rc = tpu_pod.launch_tpu(_tpu_args(), {})
    assert rc == 2
    assert "no TPU pod metadata" in capsys.readouterr().err


def test_tpu_hosts_fallback_uses_ssh(monkeypatch):
    from horovod_tpu.runner import tpu_pod
    from horovod_tpu.runner.launch import build_parser
    monkeypatch.setattr(tpu_pod, "resolve_tpu_pod", lambda: None)
    args = build_parser().parse_args(
        ["--tpu", "-H", "h0:1,h1:1", "--disable-connectivity-probe",
         "--", "python", "t.py"])
    with mock.patch.object(subprocess, "Popen") as popen:
        popen.return_value.wait.return_value = 0
        popen.return_value.stdin = mock.MagicMock()
        rc = tpu_pod.launch_tpu(args, {})
    assert rc == 0 and popen.call_count == 2
