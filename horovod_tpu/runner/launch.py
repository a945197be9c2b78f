"""``hvdtrun`` — the horovodrun-equivalent CLI.

Re-conception of ref: runner/launch.py:1-774 (parse_args :242-527,
_run_static :528, _run_elastic :621) + runner/gloo_run.py:240 launch_gloo
for the TPU process model: one worker process per TPU VM host, rendezvous
via our HTTP KV (bootstrap) + the JAX coordination service (runtime), no
MPI anywhere.

Flow (static):
  parse hosts → SlotInfo assignments (hosts.py) → start RendezvousServer →
  publish cluster spec → spawn one shell per slot (local exec or ssh) with
  the HVDT_* env contract → stream rank-prefixed output → first non-zero
  exit terminates the job (ref: gloo_run.py:134-197 terminate_all).
"""

from __future__ import annotations

import argparse
import os
import shlex
import socket
import sys
import threading
from typing import Dict, List, Optional

from . import hosts as hosts_mod
from .config_parser import add_knob_arguments, apply_config_file, env_from_args
from .http_kv import RendezvousServer, new_secret
from .safe_shell_exec import safe_execute

__all__ = ["main", "parse_args", "run_static"]

_LOCAL_NAMES = {"localhost", "127.0.0.1", "::1"}


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="hvdtrun",
        description="Launch distributed training on TPU hosts "
                    "(horovodrun-equivalent).")
    p.add_argument("-V", "--version", action="store_true", dest="version",
                   help="Print the horovod_tpu version and exit.")
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="Print build capabilities (native core, TCP data "
                        "plane, TPU visibility) and exit "
                        "(ref: horovodrun --check-build).")
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="Total number of worker processes.")
    p.add_argument("--network-interface", "--nics", dest="nics",
                   default=None,
                   help="Comma-separated NIC allowlist: the launcher "
                        "advertises its rendezvous/KV address from the "
                        "first matching interface (static and elastic), "
                        "and exports HVDT_NICS to workers.")
    p.add_argument("--disable-cache", action="store_true",
                   help="Disable the controller response cache "
                        "(HVDT_CACHE_CAPACITY=0; every collective "
                        "renegotiates, ref: --disable-cache).")
    p.add_argument("-H", "--hosts", default=None,
                   help='Comma-separated "host:slots" list.')
    p.add_argument("--hostfile", default=None,
                   help='Hostfile with "host slots=N" lines.')
    p.add_argument("-p", "--ssh-port", type=int, default=None)
    p.add_argument("--ssh-identity-file", default=None)
    p.add_argument("--coordinator-port", type=int, default=29500,
                   help="Port for the JAX coordination service on rank 0's "
                        "host.")
    p.add_argument("--start-timeout", type=float, default=600.0)
    p.add_argument("--output-filename", default=None,
                   help="Mux per-rank output into <dir>/rank.<N> files.")
    p.add_argument("--verbose", "-v", action="store_true")
    p.add_argument("--config-file", default=None,
                   help="YAML file with runtime-knob sections (see "
                        "runner/config_parser.py). Precedence: CLI > "
                        "caller env > config file > default.")
    p.add_argument("--tcp-base-port", type=int, default=40000,
                   help="First listener port for the native TCP host data "
                        "plane (used when --cpu-operations tcp).")
    p.add_argument("--no-preflight", action="store_true",
                   help="Skip the host-reachability preflight probe.")
    add_knob_arguments(p)
    # Elastic flags (ref: launch.py elastic group)
    p.add_argument("--host-discovery-script", default=None,
                   help="Executable printing current 'host:slots' lines; "
                        "enables elastic mode.")
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--slots-per-host", type=int, default=1)
    p.add_argument("--reset-limit", type=int, default=None,
                   help="Max worker resets before aborting the elastic job.")
    p.add_argument("--elastic-timeout", type=float, default=600.0,
                   help="Seconds to wait for min-np slots at each elastic "
                        "rendezvous (ref: --elastic-timeout).")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Training command, e.g. python train.py")
    args = p.parse_args(argv)
    if args.version or args.check_build:
        return args
    if not args.command:
        p.error("no training command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    return args


def _print_check_build() -> None:
    """--check-build / --version output (ref: horovodrun --check-build
    prints the framework/controller/transport capability table)."""
    import subprocess

    import horovod_tpu as hvd

    print(f"horovod_tpu v{hvd.__version__}")
    # TPU probe in a time-bounded child: this process must not touch
    # JAX (a launcher that holds the chip starves its own workers).
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax;"
             "print(any(d.platform=='tpu' for d in jax.devices()))"],
            capture_output=True, text=True, timeout=30)
        tpu = "True" in r.stdout
    except Exception:
        tpu = False
    rows = [
        ("native C++ core", hvd.native_built()),
        ("TCP host data plane", hvd.tcp_enabled()),
        ("TPU visible", tpu),
    ]
    print("\nAvailable capabilities:")
    for name, ok in rows:
        print(f"    [{'X' if ok else ' '}] {name}")
    print("\nData planes: [X] XLA collectives (jit)  "
          "[X] host eager (grouped/fused)")


def _is_local(hostname: str) -> bool:
    return (hostname in _LOCAL_NAMES
            or hostname == socket.gethostname()
            or hostname == socket.getfqdn())


def _ssh_prefix(args, hostname: str) -> str:
    opts = "-o StrictHostKeyChecking=no -o BatchMode=yes"
    if args.ssh_port:
        opts += f" -p {args.ssh_port}"
    if args.ssh_identity_file:
        opts += f" -i {shlex.quote(args.ssh_identity_file)}"
    return f"ssh {opts} {shlex.quote(hostname)}"


def _build_command(args, slot: hosts_mod.SlotInfo, base_env: Dict[str, str],
                   command: List[str]) -> (str, Dict[str, str]):
    env = dict(os.environ)
    env.update(base_env)
    env.update(slot.to_env())
    cmd = " ".join(shlex.quote(c) for c in command)
    if _is_local(slot.hostname):
        return cmd, env
    # Remote: forward the contract env explicitly through ssh.
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in {**base_env,
                                             **slot.to_env()}.items())
    return (f"{_ssh_prefix(args, slot.hostname)} "
            f"{shlex.quote(f'cd {os.getcwd()} && env {exports} {cmd}')}",
            dict(os.environ))


def knob_env_for(args) -> Dict[str, str]:
    """Resolve the runtime-knob env contract for workers (CLI > caller
    env > --config-file > default; ref: config_parser.py precedence)."""
    file_values = apply_config_file(args, getattr(args, "config_file", None))
    env = env_from_args(args, file_values)
    if getattr(args, "disable_cache", False):
        env["HVDT_CACHE_CAPACITY"] = "0"
    if getattr(args, "nics", None):
        env["HVDT_NICS"] = args.nics
    return env


def tcp_addrs_env(args, slots: List[hosts_mod.SlotInfo],
                  env: Dict[str, str]) -> Dict[str, str]:
    """Allocate the rank-ordered HVDT_TCP_ADDRS contract when the native
    TCP host data plane is selected and the operator didn't hand-set it.

    Each rank listens at ``tcp_base_port + local_rank`` on its host —
    a contiguous per-host block, as the per-set port striding requires
    (ops/tcp_backend.py)."""
    if env.get("HVDT_CPU_OPERATIONS", os.environ.get(
            "HVDT_CPU_OPERATIONS", "xla")).lower() != "tcp":
        return {}
    if env.get("HVDT_TCP_ADDRS") or os.environ.get("HVDT_TCP_ADDRS"):
        return {}
    addrs = []
    for slot in sorted(slots, key=lambda s: s.rank):
        host = "127.0.0.1" if _is_local(slot.hostname) else slot.hostname
        addrs.append(f"{host}:{args.tcp_base_port + slot.local_rank}")
    return {"HVDT_TCP_ADDRS": ",".join(addrs)}


def _nic_addr(nics: List[str]) -> Optional[str]:
    """IPv4 address of the first present interface in ``nics`` (the
    --network-interface allowlist; ref: driver_service NIC selection).
    Linux SIOCGIFADDR — returns None when none match."""
    import fcntl
    import struct

    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for nic in nics:
            try:
                packed = fcntl.ioctl(
                    s.fileno(), 0x8915,  # SIOCGIFADDR
                    struct.pack("256s", nic.strip()[:15].encode()))
                return socket.inet_ntoa(packed[20:24])
            except OSError:
                continue
    finally:
        s.close()
    return None


def preflight_reachability(args, slots: List[hosts_mod.SlotInfo],
                           addr: str, port: int) -> None:
    """Probe that every worker host can reach the launcher's rendezvous
    server before any rank is spawned — the analog of the reference's
    driver/NIC discovery (ref: runner/driver/driver_service.py:162-260,
    which probes mutually-routable interfaces).  On TPU VMs a single NIC
    carries DCN, so the failure mode worth catching is "this host can't
    reach the coordinator address at all" — fail fast, naming the host,
    instead of an opaque rendezvous timeout minutes later.
    """
    import subprocess

    probe_py = (f"import socket;"
                f"socket.create_connection(('{addr}',{port}),timeout=10);"
                f"print('ok')")
    seen = set()
    for slot in slots:
        host = slot.hostname
        if host in seen:
            continue
        seen.add(host)
        if _is_local(host):
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=10).close()
            except OSError as e:
                raise RuntimeError(
                    f"preflight: host {host!r} (local) cannot reach the "
                    f"rendezvous server at 127.0.0.1:{port} — {e!r}. "
                    f"Pass --no-preflight to skip.") from e
            continue
        cmd = (f"{_ssh_prefix(args, host)} "
               f"{shlex.quote(f'python3 -c {shlex.quote(probe_py)}')}")
        try:
            res = subprocess.run(cmd, shell=True, capture_output=True,
                                 text=True, timeout=30)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"preflight: host {host!r} did not answer the "
                f"reachability probe to {addr}:{port} within 30s")
        if res.returncode != 0 or "ok" not in res.stdout:
            raise RuntimeError(
                f"preflight: host {host!r} cannot reach the rendezvous "
                f"server at {addr}:{port} — "
                f"{(res.stderr or res.stdout).strip()[-300:]!r}. "
                f"Check that the launcher's address is routable from the "
                f"worker (wrong NIC?) or pass --no-preflight to skip.")


def run_static(args) -> int:
    """Static launch (ref: launch.py:528 _run_static + gloo_run.py:240)."""
    if args.hostfile:
        host_list = hosts_mod.parse_host_files(args.hostfile)
    elif args.hosts:
        host_list = hosts_mod.parse_hosts(args.hosts)
    else:
        host_list = [hosts_mod.HostInfo("localhost",
                                        args.num_proc or 1)]
    np_ = args.num_proc or sum(h.slots for h in host_list)
    slots = hosts_mod.get_host_assignments(host_list, np_)

    server = RendezvousServer(secret=new_secret())
    port = server.start()
    my_addr = socket.gethostbyname(socket.gethostname()) \
        if any(not _is_local(s.hostname) for s in slots) else "127.0.0.1"
    if getattr(args, "nics", None):
        # --network-interface: advertise the rendezvous on the allowed
        # NIC's address (workers then reach the coordinator over it).
        nic_addr = _nic_addr(args.nics.split(","))
        if nic_addr:
            my_addr = nic_addr
        else:
            print(f"hvdtrun: none of --network-interface {args.nics} "
                  "present on this host; using default address",
                  file=sys.stderr)
    coord_host = slots[0].hostname
    if _is_local(coord_host):
        coord_host = "127.0.0.1"
    base_env = {
        "HVDT_RENDEZVOUS_ADDR": my_addr,
        "HVDT_RENDEZVOUS_PORT": str(port),
        "HVDT_SECRET": server.secret.hex(),
        "HVDT_COORDINATOR_ADDR": f"{coord_host}:{args.coordinator_port}",
    }
    base_env.update(knob_env_for(args))
    base_env.update(tcp_addrs_env(args, slots, base_env))
    server.put_local("/cluster/size", str(np_).encode())
    if not getattr(args, "no_preflight", False):
        try:
            preflight_reachability(args, slots, my_addr, port)
        except RuntimeError:
            server.stop()
            raise

    terminate = threading.Event()
    exit_codes: Dict[int, int] = {}
    lock = threading.Lock()

    def _run_slot(slot: hosts_mod.SlotInfo):
        cmd, env = _build_command(args, slot, base_env, args.command)
        out = err = None
        if args.output_filename:
            os.makedirs(args.output_filename, exist_ok=True)
            out = open(os.path.join(args.output_filename,
                                    f"rank.{slot.rank}"), "w")
            err = out
        prefix = f"[{slot.rank}]<stdout>:" if args.verbose else ""
        code = safe_execute(cmd, env=env, stdout=out, stderr=err,
                            prefix=prefix, terminate_event=terminate)
        with lock:
            exit_codes[slot.rank] = code
        if code != 0:
            terminate.set()
        if out is not None:
            out.close()

    threads = [threading.Thread(target=_run_slot, args=(s,), daemon=True)
               for s in slots]
    for t in threads:
        t.start()
    try:
        for t in threads:
            t.join()
    except BaseException as e:
        # Whatever ends the wait (Ctrl-C, or an exception raised into a
        # programmatic caller's thread), no worker outlives its launcher.
        terminate.set()
        for t in threads:
            t.join(timeout=10)
        if isinstance(e, KeyboardInterrupt):
            return 130
        raise
    finally:
        server.stop()
    failed = {r: c for r, c in exit_codes.items() if c != 0}
    if failed:
        rank, code = sorted(failed.items())[0]
        print(f"hvdtrun: rank {rank} exited with code {code}",
              file=sys.stderr)
        return code
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # `hvdtrun serve ...` — the serving plane.  Bare: one replica,
        # direct HTTP.  With --replicas/--autoscale: the elastic serving
        # control plane (serve/autoscale.py) — rendezvous KV + replica
        # fleet + SLO router, sharing the training driver's discovery/
        # blacklist/drain machinery, e.g.
        #   hvdtrun serve --checkpoint /ckpts --replicas 3 --autoscale \
        #       --slo-p99-ms 250
        # `--engine continuous` (or HVDT_SERVE_ENGINE=continuous) swaps
        # each replica's static bucket engine for the paged-KV
        # continuous-batching LLM decode engine (serve/llm) — the fleet
        # flags compose unchanged.  Flags after `serve` are the serve
        # CLI's (see horovod_tpu/serve/__main__.py).
        from ..serve import main as serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "top":
        # `hvdtrun top ...` — live terminal view over worker
        # /timeseries endpoints (telemetry/top.py): per-rank step-time
        # sparklines, goodput, worst pod, last anomalies.  Flags after
        # `top` are the top CLI's (--endpoints/--interval/--once/
        # --event-log).
        from ..telemetry.top import main as top_main

        return top_main(argv[1:])
    if argv and argv[0] == "fleet":
        # `hvdtrun fleet <trace> ...` — trace-driven CPU simulation of
        # the bin-packing fleet scheduler (fleet/simulate.py): replay a
        # diurnal/flash-crowd/step-function traffic trace (or a trace
        # JSON) plus an optional resilience fault plan against the real
        # scheduler over a TopologySpec-priced pod fleet, e.g.
        #   hvdtrun fleet diurnal --pods 8 \
        #       --fault-plan pod_crash@step=40:pod=pod5
        # Prints the goodput-vs-SLO report as one JSON doc.
        from ..fleet.simulate import main as fleet_main

        return fleet_main(argv[1:])
    if argv and argv[0] == "lint":
        # `hvdtrun lint ...` — the static-analysis gate (collective-
        # schedule verifier + hvdt-lint rule registry + lock-order
        # graph; horovod_tpu/analysis).  Bare `hvdtrun lint` runs the
        # full --all gate; flags after `lint` are the analysis CLI's
        # (see python -m horovod_tpu.analysis --help).
        from ..analysis import main as analysis_main

        rest = argv[1:]
        return analysis_main(rest if rest else ["--all"])
    args = parse_args(argv)
    if args.version or args.check_build:
        _print_check_build()
        return 0
    if args.host_discovery_script:
        from .elastic.driver import run_elastic

        return run_elastic(args)
    return run_static(args)


if __name__ == "__main__":
    sys.exit(main())
