"""``hvdrun`` — the launcher CLI.

Reference parity: ``horovodrun`` (reference: runner/launch.py:286-594 argparse,
:806 _run; setup.py:255-257 entry point). The reference launcher spawns one
process per accelerator over SSH/MPI and wires a Gloo rendezvous. The
TPU-native model is different: JAX is single-controller-per-host SPMD, so

- single host: ONE process drives all local chips — ``hvdrun -np N cmd``
  validates N against the visible chips (or forces an N-device virtual CPU
  mesh with ``--virtual`` for development, the analogue of gloo-on-localhost);
- multi host: one process per host, each launched with coordinator env vars
  (``jax.distributed.initialize`` is the rendezvous). ``--hosts`` does this
  over SSH like the reference's gloo_run (runner/gloo_run.py:116-200).

Runtime knobs are forwarded 1:1 as HOROVOD_* env vars, mirroring the
reference's flag→env convention (launch.py:356-544).
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
from typing import List, Optional

from horovod_tpu.version import __version__


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu training program.",
        # Exact flag names only: abbreviation would defeat the config-file
        # override detection (an abbreviated flag wouldn't be recognized as
        # explicitly given, letting the file clobber it).
        allow_abbrev=False)
    p.add_argument("-v", "--version", action="version", version=__version__)
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="Total number of chips (devices) to use. Default: all "
                        "visible devices.")
    p.add_argument("--virtual", action="store_true",
                   help="Force an -np-device virtual CPU mesh (development / "
                        "CI; analogue of the reference's gloo-on-localhost).")
    p.add_argument("--tpu", action="store_true",
                   help="TPU-pod launch: resolve workers from Cloud TPU "
                        "metadata (TPU_WORKER_HOSTNAMES / GCE "
                        "worker-network-endpoints; --hosts fallback). "
                        "On a worker VM: wire rendezvous env and exec; "
                        "off-pod: ssh one controller per worker (the "
                        "scheduler-launch role of reference js_run.py / "
                        "util/lsf.py for the TPU deployment path).")
    p.add_argument("-H", "--hosts", default=None,
                   help="Comma-separated host:slots list for multi-host launch "
                        "over SSH (one controller process per host).")
    p.add_argument("--hostfile", default=None,
                   help="File with one host:slots per line.")
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--coordinator-port", type=int, default=9733)
    p.add_argument("--disable-connectivity-probe", action="store_true",
                   help="Skip the pre-launch SSH probe that verifies every "
                        "host can reach the driver and auto-discovers each "
                        "host's routable address (reference "
                        "driver_service.py NIC discovery).")
    p.add_argument("--probe-timeout", type=float, default=30.0,
                   help="Seconds to wait for all connectivity probes.")
    # Elastic mode (reference launch.py:356-594 elastic group + :689
    # _run_elastic): present --host-discovery-script switches to the
    # generation-based elastic launcher (runner/elastic_run.py).
    p.add_argument("--min-np", type=int, default=None,
                   help="Minimum world size; elastic runs stall/abort below "
                        "this (reference --min-np).")
    p.add_argument("--max-np", type=int, default=None,
                   help="Maximum world size (reference --max-np).")
    p.add_argument("--host-discovery-script", default=None,
                   help="Executable printing one 'hostname[:slots]' per "
                        "line; polled every second (reference "
                        "--host-discovery-script).")
    p.add_argument("--slots", type=int, default=None,
                   help="Default slots per discovered host (reference "
                        "--slots).")
    p.add_argument("--start-timeout", type=float, default=60.0,
                   help="Seconds to wait for --min-np slots (reference "
                        "--start-timeout).")
    p.add_argument("--reset-limit", type=int, default=None,
                   help="Max failure-driven world resets before aborting "
                        "(reference --reset-limit).")
    p.add_argument("--elastic-local", action="store_true",
                   help="Spawn all elastic workers locally regardless of "
                        "hostname (integration tests; analogue of the "
                        "reference's localhost elastic suite).")
    p.add_argument("--elastic-state-dir", default=None,
                   help="Directory for committed elastic state snapshots.")
    p.add_argument("--elastic-grace-seconds", type=float, default=None,
                   help="Seconds survivors wait at a restart barrier for "
                        "peers before declaring them failed "
                        "(HOROVOD_ELASTIC_GRACE_SECONDS).")
    p.add_argument("--output-filename", default=None,
                   help="Redirect each host's output to <file>.<host> "
                        "(reference --output-filename).")
    # Resilience (resilience/: async checkpointing + preemption).
    p.add_argument("--auto-resume", type=int, default=None, metavar="N",
                   help="Restart the run up to N times when it exits with "
                        "the resumable status (75: preemption snapshot "
                        "committed) or dies to a signal; each restart "
                        "restores from the latest committed checkpoint in "
                        "--ckpt-dir (HOROVOD_AUTO_RESUME).")
    p.add_argument("--ckpt-dir", default=None,
                   help="Checkpoint directory for the resilience "
                        "subsystem's crash-safe snapshots "
                        "(HOROVOD_CKPT_DIR).")
    p.add_argument("--ckpt-interval", default=None,
                   help="Steps between async snapshots, or 'auto' for "
                        "CheckFreq-style cadence tuning "
                        "(HOROVOD_CKPT_INTERVAL).")
    p.add_argument("--preemption-file", default=None,
                   help="Sentinel file that triggers quiesce + final "
                        "snapshot + resumable exit when touched "
                        "(HOROVOD_PREEMPTION_FILE).")
    p.add_argument("--verbose", action="store_true")
    # Knob mirrors (reference launch.py:356-544).
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--fusion-threshold", default=None,
                   help="Raw HOROVOD_FUSION_THRESHOLD value; accepts size "
                        "suffixes ('64MB') and the per-axis form "
                        "'local:64MB,cross:8MB' on hierarchical meshes.")
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--hierarchical-allreduce", action="store_true")
    p.add_argument("--torus-allreduce", action="store_true",
                   help="2D torus (local x cross) allreduce decomposition "
                        "(fork-specific, reference launch.py:396-407).")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--trace", action="store_true",
                   help="Enable the distributed span tracer on every "
                        "worker (HOROVOD_TRACE=1) with a launcher-minted "
                        "shared trace id, so all hosts' spans join one "
                        "logical trace and the leader's shutdown export "
                        "merges them onto one Perfetto timeline "
                        "(docs/tracing.md).")
    p.add_argument("--trace-dir", default=None,
                   help="Trace-artifact directory on every worker "
                        "(HOROVOD_TRACE_DIR).")
    p.add_argument("--trace-profile", default=None, metavar="SPEC",
                   help="Profile capture window, 'steps:N[@S]' "
                        "(HOROVOD_TRACE_PROFILE).")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="HTTP /metrics + /healthz server port on every "
                        "worker (HOROVOD_METRICS_PORT).")
    p.add_argument("--metrics-dump", default=None,
                   help="Periodic JSON metrics-snapshot dump path "
                        "(HOROVOD_METRICS_DUMP).")
    p.add_argument("--stall-check-disable", action="store_true")
    p.add_argument("--log-level", default=None)
    p.add_argument("--mesh-shape", default=None,
                   help="Comma-separated mesh shape, e.g. 4,2.")
    p.add_argument("--config-file", default=None,
                   help="YAML config file; explicit CLI flags win over file "
                        "values (reference --config-file, "
                        "runner/common/util/config_parser.py).")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Program and args to launch.")
    return p


def env_from_args(args) -> dict:
    env = {}
    # Per-run random secret for worker-notification HMAC auth (the
    # reference's launcher-generated secret key, runner/common/util/secret.py
    # — never the static test fallback for launched runs). Also exported into
    # THIS process's environment so driver-side notification clients (e.g.
    # the elastic driver running inside hvdrun) sign with the same key.
    from horovod_tpu.elastic.notification import SECRET_ENV, make_secret
    secret = make_secret().hex()
    env[SECRET_ENV] = secret
    os.environ[SECRET_ENV] = secret
    if args.fusion_threshold_mb is not None:
        env["HOROVOD_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024))
    if getattr(args, "fusion_threshold", None):
        if args.fusion_threshold_mb is not None:
            raise ValueError(
                "--fusion-threshold and --fusion-threshold-mb both set; "
                "pass only one")
        # Validate eagerly so a bad per-axis spec fails in the launcher,
        # not in every worker.
        from horovod_tpu.config import _parse_fusion_threshold
        _parse_fusion_threshold(args.fusion_threshold)
        env["HOROVOD_FUSION_THRESHOLD"] = args.fusion_threshold
    if args.cycle_time_ms is not None:
        env["HOROVOD_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HOROVOD_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.hierarchical_allreduce:
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    if args.torus_allreduce:
        env["HOROVOD_TORUS_ALLREDUCE"] = "1"
    if args.autotune:
        env["HOROVOD_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HOROVOD_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.timeline_filename:
        env["HOROVOD_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if getattr(args, "trace", False):
        env["HOROVOD_TRACE"] = "1"
        # Launcher-minted shared trace id: every host enables with the
        # SAME id (spans.enable(trace_id=...)), so the merged timeline
        # is one logical trace, not N accidental ones.
        env["HVD_TRACE_ID"] = os.urandom(8).hex()
    if getattr(args, "trace_dir", None):
        env["HOROVOD_TRACE_DIR"] = args.trace_dir
    if getattr(args, "trace_profile", None):
        from horovod_tpu.tracing.profile import parse_profile_spec
        parse_profile_spec(args.trace_profile)    # fail in the launcher
        env["HOROVOD_TRACE_PROFILE"] = args.trace_profile
    if args.metrics_port is not None:
        env["HOROVOD_METRICS_PORT"] = str(args.metrics_port)
    if args.metrics_dump:
        env["HOROVOD_METRICS_DUMP"] = args.metrics_dump
    if args.stall_check_disable:
        env["HOROVOD_STALL_CHECK_DISABLE"] = "1"
    if args.elastic_grace_seconds is not None:
        env["HOROVOD_ELASTIC_GRACE_SECONDS"] = str(args.elastic_grace_seconds)
    if args.auto_resume is not None:
        env["HOROVOD_AUTO_RESUME"] = str(args.auto_resume)
    if args.ckpt_dir:
        env["HOROVOD_CKPT_DIR"] = args.ckpt_dir
    if args.ckpt_interval is not None:
        from horovod_tpu.config import _parse_ckpt_interval
        _parse_ckpt_interval(args.ckpt_interval)   # fail in the launcher
        env["HOROVOD_CKPT_INTERVAL"] = str(args.ckpt_interval)
    if args.preemption_file:
        env["HOROVOD_PREEMPTION_FILE"] = args.preemption_file
    if args.log_level:
        env["HOROVOD_LOG_LEVEL"] = args.log_level
    if args.mesh_shape:
        env["HOROVOD_TPU_MESH_SHAPE"] = args.mesh_shape
    return env


def parse_hosts(hosts: Optional[str], hostfile: Optional[str]) -> List[tuple]:
    """Parse 'h1:4,h2:4' or a hostfile into [(host, slots)]
    (reference runner/common/util/hosts.py parse_hosts)."""
    entries: List[str] = []
    if hosts:
        entries = [h.strip() for h in hosts.split(",") if h.strip()]
    elif hostfile:
        with open(hostfile) as f:
            entries = [ln.strip().replace(" slots=", ":")
                       for ln in f if ln.strip()
                       and not ln.strip().startswith("#")]
    out = []
    for e in entries:
        if ":" in e:
            host, slots = e.rsplit(":", 1)
            out.append((host, int(slots)))
        else:
            out.append((e, 1))
    return out


def _launch_local(args, extra_env: dict) -> int:
    cmd = list(args.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    env = dict(os.environ)
    env.update(extra_env)
    if args.virtual:
        np_ = args.num_proc or 8
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={np_}").strip()
        env["JAX_PLATFORMS"] = "cpu"
    elif args.num_proc is not None:
        env["HVD_TPU_EXPECT_NP"] = str(args.num_proc)
    if args.verbose:
        print(f"hvdrun: exec {shlex.join(cmd)}", file=sys.stderr)

    def run_once(attempt: int) -> int:
        env["HVD_RESUME_ATTEMPT"] = str(attempt)
        return subprocess.call(cmd, env=env)

    return _supervise(run_once, args)


def _supervise(run_once, args) -> int:
    """Auto-resume supervision (resilience/preemption.py contract): a run
    exiting with the resumable status (75) committed a final snapshot on
    purpose; a signal death (negative rc) may have one from the async
    cadence. Either way the command is relaunched — workers restore from
    the latest committed checkpoint in HOROVOD_CKPT_DIR — up to
    --auto-resume/HOROVOD_AUTO_RESUME times, with HVD_RESUME_ATTEMPT
    stamped per attempt. Ordinary failures (tracebacks, bad flags) are
    NOT retried: they are deterministic bugs, not preemptions."""
    from horovod_tpu.config import knobs
    from horovod_tpu.resilience.preemption import RESUMABLE_EXIT_CODE
    auto_resume = args.auto_resume if args.auto_resume is not None else \
        int(knobs.get("HOROVOD_AUTO_RESUME"))
    attempt = 0
    while True:
        rc = run_once(attempt)
        resumable = rc == RESUMABLE_EXIT_CODE or rc < 0
        if rc == 0 or not resumable or attempt >= auto_resume:
            return rc
        attempt += 1
        how = "resumable" if rc > 0 else "to a signal"
        print(f"hvdrun: run exited {how} (rc={rc}); auto-resume "
              f"attempt {attempt}/{auto_resume}", file=sys.stderr)


def _launch_multihost(args, hosts: List[tuple], extra_env: dict) -> int:
    """One controller process per host over SSH (reference gloo_run.py
    _exec_command_fn:116-200). Host 0 is the JAX distributed coordinator."""
    cmd = list(args.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    from horovod_tpu.elastic.notification import SECRET_ENV
    coordinator = f"{hosts[0][0]}:{args.coordinator_port}"
    # Verify every host is reachable and learn each host's routable address
    # BEFORE spawning anything (ref HorovodRunDriverService NIC discovery,
    # runner/driver/driver_service.py:30,162,218). The learned address
    # becomes the host's HVD_TPU_ADVERTISE_HOST so data-service registries
    # work multi-host with no manual env preparation.
    advertise: dict = {}
    if not args.disable_connectivity_probe:
        from horovod_tpu.runner.probe import probe_hosts
        advertise = probe_hosts([h for h, _ in hosts],
                                ssh_port=args.ssh_port,
                                timeout=args.probe_timeout)
        if args.verbose:
            print(f"hvdrun: probe learned addresses {advertise}",
                  file=sys.stderr)
    cwd = os.getcwd()

    def run_once(attempt: int) -> int:
        procs = []
        for i, (host, _slots) in enumerate(hosts):
            env_pairs = dict(extra_env)
            env_pairs["HVD_TPU_COORDINATOR"] = coordinator
            env_pairs["HVD_TPU_NUM_PROCESSES"] = str(len(hosts))
            env_pairs["HVD_TPU_PROCESS_ID"] = str(i)
            env_pairs["HVD_RESUME_ATTEMPT"] = str(attempt)
            if i in advertise and "HVD_TPU_ADVERTISE_HOST" not in env_pairs:
                env_pairs["HVD_TPU_ADVERTISE_HOST"] = advertise[i]
            # The HMAC secret must NOT appear on the remote command line
            # (any local user could read it from the process list); ship it
            # on the ssh stdin instead — the remote shell reads one line
            # before exec.
            secret = env_pairs.pop(SECRET_ENV, None)
            env_str = " ".join(f"{k}={shlex.quote(v)}"
                               for k, v in env_pairs.items())
            remote = (f"cd {shlex.quote(cwd)} && env {env_str} "
                      f"{shlex.join(cmd)}")
            if secret is not None:
                remote = (f"read -r {SECRET_ENV} && export {SECRET_ENV} && "
                          + remote)
            ssh = ["ssh"]
            if args.ssh_port:
                ssh += ["-p", str(args.ssh_port)]
            full = ssh + [host, remote]
            if args.verbose:
                print(f"hvdrun: {shlex.join(full)}", file=sys.stderr)
            stdout = None
            if args.output_filename:
                stdout = open(f"{args.output_filename}.{host}", "wb")
            p = subprocess.Popen(full, stdout=stdout,
                                 stderr=subprocess.STDOUT if stdout
                                 else None,
                                 stdin=subprocess.PIPE if secret is not None
                                 else None)
            if secret is not None:
                p.stdin.write((secret + "\n").encode())
                p.stdin.flush()
            procs.append(p)
        # A resumable exit (preemption quiesce, 75) anywhere must win over
        # plain-zero exits so the supervision loop sees it; any other
        # nonzero rc wins over resumable (a crashed host is not a clean
        # preemption).
        from horovod_tpu.resilience.preemption import RESUMABLE_EXIT_CODE
        rc = 0
        saw_resumable = False
        for p in procs:
            host_rc = p.wait()
            if host_rc == RESUMABLE_EXIT_CODE:
                saw_resumable = True
            elif host_rc:
                rc = rc or host_rc
        if rc == 0 and saw_resumable:
            rc = RESUMABLE_EXIT_CODE
        return rc

    return _supervise(run_once, args)


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.config_file:
        from horovod_tpu.runner.config_file import (
            cli_overrides, load_config_file, set_args_from_config)
        raw_argv = sys.argv[1:] if argv is None else argv
        set_args_from_config(
            parser, args, load_config_file(args.config_file),
            cli_overrides(parser, raw_argv, args.command))
    extra_env = env_from_args(args)
    if args.host_discovery_script:
        if args.min_np is None:
            print("hvdrun: elastic mode requires --min-np", file=sys.stderr)
            return 2
        from horovod_tpu.runner.elastic_run import launch_elastic
        return launch_elastic(args, extra_env)
    if args.tpu:
        from horovod_tpu.runner.tpu_pod import launch_tpu
        return launch_tpu(args, extra_env)
    hosts = parse_hosts(args.hosts, args.hostfile)
    if hosts:
        return _launch_multihost(args, hosts, extra_env)
    return _launch_local(args, extra_env)


if __name__ == "__main__":
    sys.exit(main())
