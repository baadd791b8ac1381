"""Shared test helpers (imported as a plain module from tests/; the
suite runs with pytest's default prepend import mode, which puts this
directory on sys.path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def eqns(jaxpr, path=()):
    """Every equation under ``jaxpr`` with the primitives that enclose
    it: ``(path, eqn)``."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub, path + (eqn.primitive.name,))


def pallas_eqns(fn, *args):
    """The ``pallas_call`` equations ``fn`` traces, in order."""
    return [eqn for _, eqn in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def pallas_calls(fn, *args, scratch=False):
    """[(name, grid, [block shapes])] of every pallas_call ``fn`` traces;
    with ``scratch`` a fourth entry, [(scratch shape, dtype)]."""
    found = []
    for eqn in pallas_eqns(fn, *args):
        gm = eqn.params["grid_mapping"]
        call = (
            eqn.params["name"], tuple(gm.grid),
            [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
             for bm in gm.block_mappings])
        if scratch:
            invars = eqn.params["jaxpr"].invars
            held = invars[len(invars) - gm.num_scratch_operands:]
            call += ([(v.aval.shape, str(v.aval.dtype)) for v in held],)
        found.append(call)
    return found


def described_chip(monkeypatch):
    """The sharding of one chip of a v5e that is described, not attached,
    with jax told its backend is a TPU: what the ``*_lower_for_the_chip``
    tests compile for (interpret mode cannot see tiling or VMEM).  Call it
    from a test's body, never while a module is imported.  Each kernel's
    cases stand in that kernel's test file, so under several workers
    several processes load the TPU's library at once: the driver's command
    allows that (``ALLOW_MULTIPLE_LIBTPU_LOAD=1``); without it only the
    first process gets the library and the other files' cases skip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no TPU topology to compile for: {e}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return SingleDeviceSharding(topo.devices[0])


def sp_sharded(mesh, fn):
    """jit(shard_map) over the sp axis with the specs the SP paths use."""
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))


def mesh_map(fn, sizes, names, in_axes):
    """``fn`` on every device of a mesh of the virtual devices, ``sizes``
    by ``names``, as a ``pmap`` nested in a ``pmap`` read it: an argument
    whose ``in_axes`` is 0 comes with the mesh's dimensions leading, a
    piece a device, one that is None is the same everywhere, and every
    result comes back with the mesh's dimensions leading (index a replica
    out of it on the host: the mesh's axes are explicit)."""
    from jax.sharding import PartitionSpec as P
    piece, mine = P(*names), (0,) * len(sizes)

    def on_a_device(*args):
        args = [x if axis is None
                else jax.tree_util.tree_map(lambda a: a[mine], x)
                for x, axis in zip(args, in_axes)]
        return jax.tree_util.tree_map(
            lambda r: jnp.asarray(r)[(None,) * len(sizes)], fn(*args))

    return jax.jit(jax.shard_map(
        on_a_device, mesh=jax.make_mesh(tuple(sizes), tuple(names)),
        out_specs=piece, check_vma=False,
        in_specs=tuple(P() if axis is None else piece for axis in in_axes)))


def free_port() -> int:
    """An OS-assigned free TCP port for a multi-process launch.

    Fixed per-test ports collided (two tests shared 29567) and raced
    with late-exiting workers from earlier launches; binding port 0
    lets the kernel pick. The tiny close-to-use window is a far
    smaller risk than cross-test collisions, and SO_REUSEADDR on the
    coordination service side tolerates TIME_WAIT.
    """
    import socket
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        # wildcard bind: the services this allocates for (JsonRpcServer,
        # coordination service) bind 0.0.0.0, so probing only loopback
        # could hand out a port someone holds on a real interface
        s.bind(("", 0))
        return s.getsockname()[1]


def random_entry_sigs(rng, n):
    """Random fusion EntrySig stream shared by the native-parity and
    planner-invariant fuzz suites (one generator, one distribution).
    ``rng`` is a ``random.Random`` (inclusive randint)."""
    from horovod_tpu.ops import fusion
    sigs = []
    for i in range(n):
        op = rng.choice(["allreduce", "allreduce", "allreduce",
                         "allgather", "broadcast", "alltoall"])
        group = rng.choice([-1, -1, -1, 1, 2])
        sigs.append(fusion.EntrySig(
            name=f"tensor.{rng.randint(0, n)}.{i}",
            op_type=op,
            reduce_op=rng.choice(["average", "sum"]),
            dtype=rng.choice(["float32", "bfloat16", "int32"]),
            shape=(rng.randint(1, 2048), rng.choice([1, 8])),
            process_set_id=rng.choice([0, 0, 0, 1]),
            stacked=rng.random() < 0.2,
            group_id=group if op == "allreduce" else -1,
            prescale=rng.choice([None, None, 0.5]),
            postscale=rng.choice([None, None, 2.0]),
        ))
    return sigs


# ---------------------------------------------- attention and its kernels

def dense_reference(q, k, v, causal=True):
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def make_qkv(B, T, H, Hkv, D, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, T, H, D), dtype)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), dtype)
    return q, k, v


def flash_kernel_counts():
    from horovod_tpu import metrics
    family = metrics.registry().to_dict().get("hvd_flash_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"],
             s["labels"]["layout"]): s["value"]
            for s in family.get("series", [])}


def flash_grew(before):
    """The series of ``hvd_flash_kernel_total`` that moved since
    ``before = flash_kernel_counts()``."""
    after = flash_kernel_counts()
    return {key for key in after if after[key] != before.get(key, 0)}


def flash_grad_all(q, k, v, causal):
    from horovod_tpu.ops import flash_attention as fa
    return jax.grad(lambda q, k, v: (fa.flash_attention(
        q, k, v, causal=causal).astype(jnp.float32) ** 2).sum(),
        (0, 1, 2))(q, k, v)


def kernels_by_place(fn, *args):
    """``[(enclosing primitives, kernel name)]`` of every pallas_call."""
    return [(path, eqn.params["name"])
            for path, eqn in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def block_diffusion_ranges(L, bk):
    """[xt ; x0]: xt sees its own block of xt and x0's earlier blocks; x0
    sees x0's own and earlier blocks."""
    block = np.arange(L) // bk
    r = np.zeros((2 * L, 4), np.int32)
    r[:L, 0], r[:L, 1] = block * bk, (block + 1) * bk
    r[:L, 2], r[:L, 3] = L, L + block * bk
    r[L:, 0], r[L:, 1] = L, L + (block + 1) * bk
    return r
