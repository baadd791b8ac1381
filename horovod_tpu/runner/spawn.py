"""Worker spawning: command construction + process supervision.

Reference parity: ``horovod/runner/gloo_run.py`` (per-slot worker exec with
the env contract pointing at the rendezvous) and ``mpi_run.py`` (remote
command construction — we assert the *generated command line* in tests the
same way ``test/single/test_run.py`` does).  Remote hosts are reached over
ssh like the reference's bootstrap; localhost workers are plain
subprocesses.
"""

from __future__ import annotations

import glob
import os
import shlex
import signal
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Sequence

from . import secret as _secret
from .hosts import SlotAssignment

LOCAL_NAMES = {"localhost", "127.0.0.1", "::1"}

SSH_OPTS = ["-o", "StrictHostKeyChecking=no", "-o", "BatchMode=yes"]


def is_local(hostname: str) -> bool:
    import socket
    return (hostname in LOCAL_NAMES
            or hostname == socket.gethostname()
            or hostname == socket.getfqdn())


def ensure_job_secret(base_env: Optional[Dict[str, str]] = None) -> str:
    """The job's control-plane secret, minting one on first launch.

    Looks in ``base_env`` then ``os.environ``; a freshly minted key is
    published to ``os.environ`` so launcher-side RPC (and later spawns)
    sign with the same key the workers receive.
    """
    key = ((base_env or {}).get(_secret.SECRET_ENV)
           or os.environ.get(_secret.SECRET_ENV))
    if not key:
        key = _secret.make_secret_key()
    os.environ[_secret.SECRET_ENV] = key
    return key


# libtpu's grid of one-chip processes for a host whose chips are all taken
# by local slots, keyed by slots on the host.  2x2 is the v5e four-chip
# host this was proven on (CHANGES.md PR 21); jax's own multi-process test
# launcher uses the same contract.
TPU_PROCESS_BOUNDS = {4: "2,2,1"}


def _chip_per_slot(slot: SlotAssignment) -> bool:
    return slot.cross_size == 1 and slot.local_size in TPU_PROCESS_BOUNDS


def tpu_chip_env(slot: SlotAssignment, coordinator_port: int
                 ) -> Dict[str, str]:
    """libtpu's environment giving one local slot a chip of its own:
    visible chip, per-process and process bounds, the local processes'
    addresses and this one's port and task id.  Empty when the slot is
    alone on its host (one process drives every chip there) and for
    layouts in which nothing is known to work — :func:`chips_contested`
    is the launcher's answer to those.  Means nothing to a CPU backend.

    libtpu numbers the processes by their chips' coordinates, not by
    task id, and which chip sits where differs from host to host: so
    ``jax.process_index()``, and with it ``hvd.rank()``, is some
    permutation of the slots' ranks.
    """
    if not _chip_per_slot(slot):
        return {}
    ports = [coordinator_port + 1 + i for i in range(slot.local_size)]
    return {
        "TPU_VISIBLE_CHIPS": str(slot.local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": TPU_PROCESS_BOUNDS[slot.local_size],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[slot.local_rank]),
        "CLOUD_TPU_TASK_ID": str(slot.local_rank),
    }


def chips_contested(slots: List[SlotAssignment],
                    env: Dict[str, str]) -> Optional[str]:
    """Why starting ``slots`` would set local children fighting over this
    host's chips, or None.  Looks at device nodes, never at jax: a
    launcher that touched the chip would hold it against its children."""
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    nodes = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if not nodes:
        return None
    for slot in slots:
        if (is_local(slot.hostname) and slot.local_size > 1
                and (slot.local_size > len(nodes)
                     or not _chip_per_slot(slot))):
            return (
                f"{slot.local_size} processes on {slot.hostname}, which has "
                f"{len(nodes)} TPU chip(s), would fight over them: one "
                f"chip per process is set up for single-host jobs with "
                f"{sorted(TPU_PROCESS_BOUNDS)} local slots only.  Run one "
                f"process per host (it drives every chip), or set "
                f"JAX_PLATFORMS=cpu for a CPU run.")
    return None


def worker_env(slot: SlotAssignment, coordinator_addr: str,
               coordinator_port: int,
               base_env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The full §3.4 environment contract for one worker."""
    env = dict(base_env if base_env is not None else os.environ)
    env.update(slot.to_env())
    env.update(tpu_chip_env(slot, coordinator_port))
    env.update({
        # reference names kept for script compatibility; the address points
        # at the JAX coordination service, not a Gloo store
        "HOROVOD_GLOO_RENDEZVOUS_ADDR": coordinator_addr,
        "HOROVOD_GLOO_RENDEZVOUS_PORT": str(coordinator_port),
        "HOROVOD_CONTROLLER": "jax",
        "HOROVOD_NUM_PROCESSES": str(slot.size),
        "HOROVOD_PROCESS_ID": str(slot.rank),
    })
    return env


def remote_command(slot: SlotAssignment, command: Sequence[str],
                   env: Dict[str, str], cwd: str) -> List[str]:
    """Build the ssh command line for a remote worker (reference: mpi_run /
    gloo_run remote exec).  Only HOROVOD_*/JAX_/XLA_ vars are forwarded —
    the reference forwards an explicit allowlist via ``-x`` for the same
    reason (remote shells own the rest of their environment)."""
    forwarded = {k: v for k, v in env.items()
                 if k.startswith(("HOROVOD_", "JAX_", "XLA_", "TPU_",
                                  "PYTHONPATH", "LIBTPU"))}
    # the job secret must NOT ride the ssh argv (visible in ps/procfs on
    # both hosts); it is delivered on the remote shell's stdin instead —
    # see the `read` prefix below and the stdin write in spawn_workers
    has_secret = forwarded.pop(_secret.SECRET_ENV, None) is not None
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in sorted(forwarded.items()))
    remote = f"cd {shlex.quote(cwd)} && env {exports} " + " ".join(
        shlex.quote(c) for c in command)
    if has_secret:
        remote = (f"IFS= read -r {_secret.SECRET_ENV} && "
                  f"export {_secret.SECRET_ENV} && " + remote)
    return ["ssh", *SSH_OPTS, slot.hostname, remote]


class WorkerProcess:
    def __init__(self, slot: SlotAssignment, popen: subprocess.Popen):
        self.slot = slot
        self.popen = popen
        self.pump: Optional[threading.Thread] = None


def _pump_output(proc: WorkerProcess, prefix: bool, out_file=None):
    stream = proc.popen.stdout
    tag = f"[{proc.slot.rank}]<{proc.slot.hostname}>"
    for raw in iter(stream.readline, b""):
        line = raw.decode(errors="replace")
        if out_file is not None:
            out_file.write(line)
            out_file.flush()
        else:
            sys.stdout.write(f"{tag}: {line}" if prefix else line)
            sys.stdout.flush()


def spawn_workers(slots: List[SlotAssignment], command: Sequence[str],
                  coordinator_addr: str, coordinator_port: int,
                  prefix_output: bool = True,
                  output_filename: Optional[str] = None,
                  base_env: Optional[Dict[str, str]] = None,
                  kv_server=None,
                  network_interface: Optional[str] = None
                  ) -> List[WorkerProcess]:
    procs: List[WorkerProcess] = []
    cwd = os.getcwd()
    # one control-plane secret per job (upstream mints in the launcher and
    # distributes via the env): published launcher-side too so this
    # process's RPC signs with the same key the workers verify against
    secret_key = ensure_job_secret(base_env)
    kv_envs: Dict[str, Dict[str, str]] = {}
    if kv_server is not None:
        # advertise the launcher-hosted KV server (runner/kv.py) with the
        # same NIC-aware address selection as the other local services;
        # one lookup per distinct hostname
        from .kv import kv_env_for
        kv_envs = {h: kv_env_for(h, is_local, kv_server,
                                 interface=network_interface)
                   for h in {s.hostname for s in slots}}
    for slot in slots:
        env = worker_env(slot, coordinator_addr, coordinator_port, base_env)
        env.setdefault(_secret.SECRET_ENV, secret_key)
        env.update(kv_envs.get(slot.hostname, {}))
        if is_local(slot.hostname):
            cmd, popen_env, stdin_data = list(command), env, None
        else:
            cmd, popen_env = remote_command(slot, command, env, cwd), None
            # secret via stdin, never argv (see remote_command)
            stdin_data = (env[_secret.SECRET_ENV] + "\n").encode()
        popen = subprocess.Popen(
            cmd, env=popen_env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            stdin=subprocess.PIPE if stdin_data else subprocess.DEVNULL,
            start_new_session=True)
        if stdin_data:
            try:
                popen.stdin.write(stdin_data)
                popen.stdin.flush()
            except OSError:
                pass  # worker died at exec; the reaper reports it
            popen.stdin.close()
        proc = WorkerProcess(slot, popen)
        out_file = (open(f"{output_filename}.{slot.rank}", "w")
                    if output_filename else None)
        proc.pump = threading.Thread(
            target=_pump_output, args=(proc, prefix_output, out_file),
            daemon=True)
        proc.pump.start()
        procs.append(proc)
    return procs


def wait_workers(procs: List[WorkerProcess],
                 timeout: Optional[float] = None) -> int:
    """Wait for all workers; on first failure terminate the rest.

    Returns the exit code to propagate (0 iff every worker exited 0) —
    the reference's gloo_run semantics.
    """
    exit_code = 0
    pending = list(procs)
    try:
        while pending:
            for p in list(pending):
                try:
                    rc = p.popen.wait(timeout=0.2)
                except subprocess.TimeoutExpired:
                    continue
                pending.remove(p)
                if rc != 0 and exit_code == 0:
                    exit_code = rc
                    sys.stderr.write(
                        f"hvdrun: worker rank {p.slot.rank} "
                        f"({p.slot.hostname}) exited with {rc}; "
                        f"terminating remaining workers\n")
                    for q in pending:
                        _terminate(q)
    except KeyboardInterrupt:
        for q in pending:
            _terminate(q)
        exit_code = 128 + signal.SIGINT
    for p in procs:
        if p.pump is not None:
            p.pump.join(timeout=2)
    return exit_code


def _terminate(p: WorkerProcess, grace: float = 5.0):
    if p.popen.poll() is not None:
        return
    try:
        os.killpg(p.popen.pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError):
        return
    try:
        p.popen.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.popen.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
