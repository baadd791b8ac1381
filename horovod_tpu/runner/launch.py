"""``hvdrun`` CLI (reference: ``horovodrun``, horovod/runner/launch.py §3.4).

Flags mirror the reference where the concept survives on TPU: ``-np``,
``-H``/``--hostfile``, ``--output-filename``, ``--verbose``,
``--start-timeout``, ``--disable-cache`` analogs via env.  MPI/Gloo
selection flags are gone: the rendezvous is always the JAX coordination
service.  Elastic flags (``--min-np``/``--max-np``/
``--host-discovery-script``) hand off to the elastic driver.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from . import spawn
from .hosts import assign_slots, effective_hosts

DEFAULT_PORT = 29410


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu job across hosts/slots "
                    "(TPU-native horovodrun).")
    p.add_argument("-np", "--num-proc", dest="np", type=int, default=None,
                   help="total number of worker processes")
    p.add_argument("-H", "--hosts", dest="hosts", default=None,
                   help="comma-separated host:slots list, e.g. a:4,b:4")
    p.add_argument("--hostfile", default=None,
                   help="hostfile with 'hostname slots=N' lines")
    p.add_argument("-p", "--port", type=int, default=DEFAULT_PORT,
                   help="coordination-service port on the first host")
    p.add_argument("--output-filename", default=None,
                   help="redirect each worker's output to FILE.<rank>")
    p.add_argument("--no-prefix-output", action="store_true",
                   help="do not prefix worker output with [rank]<host>")
    p.add_argument("--start-timeout", type=float, default=600.0,
                   help="seconds to wait for the job to finish rendezvous")
    p.add_argument("--network-interface", default=None,
                   help="local interface whose address remote workers "
                        "dial for the coordination service (reference: "
                        "horovodrun --network-interface; default: "
                        "HOROVOD_NETWORK_INTERFACE env, else the "
                        "route toward the first remote host)")
    p.add_argument("--verbose", "-v", action="store_true")
    # elastic (reference: --min-np/--max-np/--host-discovery-script)
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None,
                   help="executable printing current 'host:slots' lines; "
                        "enables elastic mode")
    p.add_argument("--check-build", action="store_true",
                   help="print framework/feature availability and exit "
                        "(reference: horovodrun --check-build)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="the training command, e.g. python train.py")
    args = p.parse_args(argv)
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if args.check_build:
        return args
    if not args.command:
        p.error("no command given")
    if args.np is None and not args.host_discovery_script:
        p.error("-np is required (or use --host-discovery-script)")
    return args


def check_build(out=None) -> int:
    """Print the feature matrix (reference: ``horovodrun --check-build``
    lists built frameworks/controllers/ops)."""
    out = out or sys.stdout

    def probe(fn):
        try:
            return bool(fn())
        except Exception:  # noqa: BLE001 - availability probe
            return False

    def has_module(name):
        import importlib.util
        return importlib.util.find_spec(name) is not None

    def native_built():
        from ..native import loader
        # report-only: never kick off a compile from a status command
        return loader.load(auto_build=False) is not None

    def flash_ok():
        from jax.experimental import pallas  # noqa: F401
        return True

    def _tf_bridge_built():
        # report-only: a built artifact on disk, no compile kicked off
        import horovod_tpu.tensorflow._xla_bridge as bridge
        return os.path.exists(bridge._OUT)

    import horovod_tpu
    checks = [
        ("JAX (XLA collectives data plane)", lambda: has_module("jax")),
        ("Torch adapter", lambda: has_module("torch")),
        ("TensorFlow adapter", lambda: has_module("tensorflow")),
        ("Keras callbacks", lambda: has_module("tensorflow")),
        ("MXNet adapter", lambda: has_module("mxnet")),
        ("Native C++ core (_hvd_core)", native_built),
        ("TF XLA op bridge (jit_compile collectives)", _tf_bridge_built),
        ("Pallas kernels (flash attention, fused xent)", flash_ok),
        ("Elastic training", lambda: has_module("horovod_tpu.elastic")),
        ("Estimators (Torch/Keras)",
         lambda: has_module("horovod_tpu.estimator")),
        ("Lightning estimator", lambda: has_module("lightning")
         or has_module("pytorch_lightning")),
    ]
    print(f"horovod_tpu v{horovod_tpu.__version__}:", file=out)
    print("\nAvailable features:", file=out)
    for name, fn in checks:
        mark = "X" if probe(fn) else " "
        print(f"    [{mark}] {name}", file=out)
    return 0


def _coordinator_addr(hosts, interface: Optional[str] = None) -> str:
    from .network import coordinator_addr
    return coordinator_addr([h.hostname for h in hosts], spawn.is_local,
                            interface=interface)


def run_launcher(args: argparse.Namespace) -> int:
    if args.check_build:
        return check_build()
    if args.host_discovery_script:
        from ..elastic.driver import run_elastic_launcher
        return run_elastic_launcher(args)
    hosts = effective_hosts(args.hosts, args.hostfile, args.np)
    slots = assign_slots(hosts, args.np)
    contested = spawn.chips_contested(slots, os.environ)
    if contested:
        print(f"hvdrun: {contested}", file=sys.stderr)
        return 2
    addr = _coordinator_addr(hosts, args.network_interface)
    if args.verbose:
        for s in slots:
            print(f"hvdrun: rank {s.rank} -> {s.hostname} "
                  f"(local {s.local_rank}/{s.local_size})", file=sys.stderr)
        print(f"hvdrun: coordinator {addr}:{args.port}", file=sys.stderr)
    # interface-aware KV advertisement matches the coordinator address
    # above; hosted_kv mints the job secret before the server binds
    from . import kv as _kv
    with _kv.hosted_kv(expected_procs=len(slots)) as kv_server:
        procs = spawn.spawn_workers(
            slots, args.command, addr, args.port,
            prefix_output=not args.no_prefix_output,
            output_filename=args.output_filename,
            base_env=dict(os.environ), kv_server=kv_server,
            network_interface=args.network_interface)
        return spawn.wait_workers(procs, timeout=args.start_timeout)


def main(argv: Optional[List[str]] = None) -> int:
    return run_launcher(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
