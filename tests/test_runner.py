"""Launcher tests (reference: test/single/test_run.py — assert generated
command lines / env contracts without launching; plus real 2-process
localhost launches, the reference's test_parallel style)."""

import os
import sys

import pytest

from _helpers import free_port

from horovod_tpu.runner import parse_args
from horovod_tpu.runner.hosts import (
    HostInfo, SlotAssignment, assign_slots, effective_hosts, parse_hostfile,
    parse_hosts)
from horovod_tpu.runner import spawn
from horovod_tpu.runner.spawn import remote_command, worker_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- arg parsing (reference: test_run.py parse tests) -----------------------

def test_parse_args_basic():
    a = parse_args(["-np", "4", "-H", "a:2,b:2", "python", "train.py"])
    assert a.np == 4 and a.hosts == "a:2,b:2"
    assert a.command == ["python", "train.py"]


def test_parse_args_separator_and_defaults():
    a = parse_args(["-np", "2", "--", "python", "train.py", "--lr", "0.1"])
    assert a.command == ["python", "train.py", "--lr", "0.1"]
    assert a.hosts is None and a.hostfile is None


def test_parse_args_requires_np_and_command():
    with pytest.raises(SystemExit):
        parse_args(["python", "train.py"])
    with pytest.raises(SystemExit):
        parse_args(["-np", "2"])


# --- host parsing ----------------------------------------------------------

def test_parse_hosts():
    assert parse_hosts("a:4,b:2") == [HostInfo("a", 4), HostInfo("b", 2)]
    assert parse_hosts("solo") == [HostInfo("solo", 1)]


def test_parse_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("# comment\nnode1 slots=4\nnode2 2\nnode3\n")
    assert parse_hostfile(str(hf)) == [
        HostInfo("node1", 4), HostInfo("node2", 2), HostInfo("node3", 1)]


def test_effective_hosts_default_localhost():
    assert effective_hosts(None, None, 8) == [HostInfo("localhost", 8)]
    with pytest.raises(ValueError):
        effective_hosts("a:1", "file", 1)


# --- slot assignment (host-major, reference order) -------------------------

def test_assign_slots_host_major():
    slots = assign_slots([HostInfo("a", 2), HostInfo("b", 2)], 4)
    assert [(s.rank, s.hostname, s.local_rank, s.cross_rank)
            for s in slots] == [
        (0, "a", 0, 0), (1, "a", 1, 0), (2, "b", 0, 1), (3, "b", 1, 1)]
    assert all(s.size == 4 and s.cross_size == 2 for s in slots)


def test_assign_slots_partial_last_host():
    slots = assign_slots([HostInfo("a", 4), HostInfo("b", 4)], 5)
    assert slots[4].hostname == "b" and slots[4].local_size == 1
    assert slots[0].local_size == 4


def test_assign_slots_overflow():
    with pytest.raises(ValueError, match="exceeds"):
        assign_slots([HostInfo("a", 2)], 3)


# --- env contract (§3.4) ---------------------------------------------------

def test_worker_env_contract():
    slot = SlotAssignment(rank=3, size=8, local_rank=1, local_size=4,
                          cross_rank=0, cross_size=2, hostname="a")
    env = worker_env(slot, "10.0.0.1", 29410, base_env={"PATH": "/bin"})
    assert env["HOROVOD_RANK"] == "3"
    assert env["HOROVOD_SIZE"] == "8"
    assert env["HOROVOD_LOCAL_RANK"] == "1"
    assert env["HOROVOD_LOCAL_SIZE"] == "4"
    assert env["HOROVOD_CROSS_RANK"] == "0"
    assert env["HOROVOD_CROSS_SIZE"] == "2"
    assert env["HOROVOD_HOSTNAME"] == "a"
    assert env["HOROVOD_GLOO_RENDEZVOUS_ADDR"] == "10.0.0.1"
    assert env["HOROVOD_GLOO_RENDEZVOUS_PORT"] == "29410"
    assert env["HOROVOD_CONTROLLER"] == "jax"
    assert env["HOROVOD_NUM_PROCESSES"] == "8"
    assert env["HOROVOD_PROCESS_ID"] == "3"
    assert env["PATH"] == "/bin"  # base env preserved


def test_worker_env_gives_each_local_slot_its_own_chip():
    """Four slots on one host: libtpu's one-chip-per-process contract,
    distinct chip, port and task id per slot.  A slot alone on its host
    (it drives every chip) and a multi-host job get nothing."""
    slots = assign_slots([HostInfo("localhost", 4)], 4)
    envs = [worker_env(s, "127.0.0.1", 29410, base_env={}) for s in slots]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"] == ",".join(
            f"localhost:{x['TPU_PROCESS_PORT']}" for x in envs)
    alone = assign_slots([HostInfo("localhost", 1)], 1)[0]
    two_hosts = assign_slots([HostInfo("a", 4), HostInfo("b", 4)], 8)[0]
    for slot in (alone, two_hosts):
        assert not any(k.startswith("TPU_")
                       for k in worker_env(slot, "h", 1, base_env={}))


def test_launcher_refuses_local_slots_that_would_share_chips(
        monkeypatch, capsys):
    """Two local slots on a host with TPU device nodes and no chip-per-
    process layout: hvdrun says why and starts nothing.  The same job
    sent to the CPU is not its business."""
    import glob
    monkeypatch.setattr(
        glob, "glob",
        lambda pat: ["/dev/accel0", "/dev/accel1"] if "accel" in pat else [])
    slots = assign_slots([HostInfo("localhost", 2)], 2)
    assert spawn.chips_contested(slots, {"JAX_PLATFORMS": "cpu"}) \
        is None
    assert "has 2 TPU chip(s)" in spawn.chips_contested(slots, {})
    four = assign_slots([HostInfo("localhost", 4)], 4)
    assert "has 2 TPU chip(s)" in spawn.chips_contested(four, {})
    monkeypatch.setattr(
        glob, "glob",
        lambda pat: [f"/dev/vfio/{i}" for i in range(4)] if "vfio" in pat
        else [])
    assert spawn.chips_contested(four, {}) is None

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(spawn, "spawn_workers", lambda *a, **k: pytest.fail(
        "spawned despite contested chips"))
    from horovod_tpu.runner import launch
    assert launch.main(["-np", "2", "python", "train.py"]) == 2
    assert "one chip per process" in capsys.readouterr().err


def test_remote_command_construction():
    """Assert the generated ssh command line (reference: mpirun cmdline
    asserts in test_run.py)."""
    slot = SlotAssignment(rank=2, size=4, local_rank=0, local_size=2,
                          cross_rank=1, cross_size=2, hostname="nodeb")
    env = {"HOROVOD_RANK": "2", "SECRET": "x", "PYTHONPATH": "/repo",
           "XLA_FLAGS": "--foo"}
    cmd = remote_command(slot, ["python", "train.py"], env, "/work dir")
    assert cmd[0] == "ssh"
    assert "nodeb" in cmd
    remote = cmd[-1]
    assert remote.startswith("cd '/work dir' && env ")
    assert "HOROVOD_RANK=2" in remote
    assert "PYTHONPATH=/repo" in remote
    assert "XLA_FLAGS=--foo" in remote
    assert "SECRET" not in remote          # only allowlisted vars forwarded
    assert remote.endswith("python train.py")


# --- real multi-process launches (localhost, CPU platform) ------------------

def _run_env():
    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        # keep worker JAX quiet and CPU-only, one device per process
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }


def test_run_api_two_process_topology():
    import helpers_runner
    from horovod_tpu.runner import run
    results = run(helpers_runner.topology_fn, np=2, env=_run_env(),
                  port=free_port())
    assert len(results) == 2
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["size"] == 2 for r in results)
    assert all(r["process_count"] == 2 for r in results)


def test_run_api_real_cross_process_collective():
    import helpers_runner
    from horovod_tpu.runner import run
    results = run(helpers_runner.cross_process_sum_fn, np=2, env=_run_env(),
                  port=free_port())
    # sum of 0*10 + 1*10 computed via a jitted global reduction
    assert all(r["sum"] == 10.0 for r in results)
    assert all(r["procs"] == 2 for r in results)


def test_run_api_worker_failure_propagates():
    import helpers_runner
    from horovod_tpu.runner import run
    with pytest.raises(RuntimeError, match="failed with exit code"):
        run(helpers_runner.failing_fn, np=2, env=_run_env(), port=free_port())


def test_check_build_flag(capsys):
    """hvdrun --check-build prints the feature matrix and exits 0
    (reference: horovodrun --check-build)."""
    from horovod_tpu.runner import launch
    args = launch.parse_args(["--check-build"])
    assert args.check_build
    rc = launch.run_launcher(args)
    out = capsys.readouterr().out
    assert rc == 0
    assert "Available features" in out
    assert "[X] JAX" in out
    assert "Torch adapter" in out


# --- network-interface selection (reference: runner/util/network.py) --------

def test_list_interfaces_has_loopback():
    from horovod_tpu.runner import network
    ifaces = network.list_interfaces()
    assert ifaces.get("lo") == "127.0.0.1"


def test_resolve_interface_names_candidates():
    from horovod_tpu.runner import network
    assert network.resolve_interface("lo") == "127.0.0.1"
    with pytest.raises(ValueError, match="lo"):
        network.resolve_interface("no-such-if0")


def test_routable_source_addr_route_lookup():
    from horovod_tpu.runner import network
    # loopback routes from loopback; no packets are sent either way
    assert network.routable_source_addr("127.0.0.1") == "127.0.0.1"
    assert network.routable_source_addr("definitely-not-a-host.invalid") \
        is None


def test_coordinator_addr_selection_order(monkeypatch):
    from horovod_tpu.runner import network
    from horovod_tpu.runner.spawn import is_local

    # remote first host: the hostfile name is the service address
    assert network.coordinator_addr(
        ["nodeA", "localhost"], is_local) == "nodeA"
    # local-only job: hostname (loopback routing)
    import socket as s
    assert network.coordinator_addr(
        ["localhost"], is_local) == s.gethostname()
    # explicit interface beats detection
    assert network.coordinator_addr(
        ["localhost", "nodeB"], is_local, interface="lo") == "127.0.0.1"
    # env contract form
    monkeypatch.setenv("HOROVOD_NETWORK_INTERFACE", "lo")
    assert network.coordinator_addr(
        ["localhost", "nodeB"], is_local) == "127.0.0.1"
    monkeypatch.delenv("HOROVOD_NETWORK_INTERFACE")
    # local first host + remote workers: source-route toward the remote
    monkeypatch.setattr(network, "routable_source_addr",
                        lambda h, port=1: "10.0.0.7")
    assert network.coordinator_addr(
        ["localhost", "nodeB"], is_local) == "10.0.0.7"
    # detection failure falls back to hostname
    monkeypatch.setattr(network, "routable_source_addr",
                        lambda h, port=1: None)
    assert network.coordinator_addr(
        ["localhost", "nodeB"], is_local) == s.gethostname()


def test_local_service_addr(monkeypatch):
    from horovod_tpu.runner import network
    from horovod_tpu.runner.spawn import is_local
    import socket as s
    assert network.local_service_addr("localhost", is_local) \
        == s.gethostname()
    assert network.local_service_addr("nodeB", is_local,
                                      interface="lo") == "127.0.0.1"
    monkeypatch.setattr(network, "routable_source_addr",
                        lambda h, port=1: "10.0.0.9")
    assert network.local_service_addr("nodeB", is_local) == "10.0.0.9"


def test_parse_args_network_interface():
    a = parse_args(["-np", "2", "--network-interface", "eth1",
                    "python", "x.py"])
    assert a.network_interface == "eth1"
    from horovod_tpu.runner.launch import _coordinator_addr
    from horovod_tpu.runner.hosts import HostInfo
    assert _coordinator_addr([HostInfo("localhost", 2)],
                             interface="lo") == "127.0.0.1"
