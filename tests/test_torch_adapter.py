"""Torch framework adapter tests.

Reference parity: ``test/parallel/test_torch.py`` (SURVEY.md §4) — op ×
dtype coverage, DistributedOptimizer equivalence, parameter/optimizer
state broadcast — on the 8-device virtual mesh (single process) plus a
REAL 2-process DP training equivalence run.
"""

import os

import numpy as np
import pytest

from _helpers import free_port
import torch

import helpers_runner
from horovod_tpu.runner import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- tensor collectives -----------------------------------------------------

def test_allreduce_sum_and_average(thvd, n_workers):
    t = torch.arange(6, dtype=torch.float32).reshape(2, 3)
    out = thvd.allreduce(t, op=thvd.Sum, name="t_sum")
    assert torch.allclose(out, t * n_workers)
    out = thvd.allreduce(t, name="t_avg")  # default average
    assert torch.allclose(out, t)
    assert out.dtype == t.dtype


@pytest.mark.parametrize("dtype", [torch.float32, torch.float64,
                                   torch.int32, torch.int64,
                                   torch.bfloat16])
def test_allreduce_dtypes(thvd, n_workers, dtype):
    t = torch.ones(4, dtype=dtype)
    out = thvd.allreduce(t, op=thvd.Sum, name=f"dt_{dtype}")
    assert out.dtype == dtype
    assert torch.allclose(out.float(), torch.full((4,), float(n_workers)))


def test_allreduce_async_poll_synchronize(thvd, n_workers):
    t = torch.ones(3)
    h = thvd.allreduce_async(t, op=thvd.Sum, name="async_t")
    h.wait(10)
    assert h.poll()
    out = thvd.synchronize(h)
    assert torch.allclose(out, t * n_workers)


def test_grouped_allreduce(thvd, n_workers):
    ts = [torch.ones(2) * (i + 1) for i in range(3)]
    outs = thvd.grouped_allreduce(ts, op=thvd.Sum, name="grp")
    for i, o in enumerate(outs):
        assert torch.allclose(o, torch.full((2,), float((i + 1) * n_workers)))


def test_allgather(thvd, n_workers):
    t = torch.arange(2, dtype=torch.float32)
    out = thvd.allgather(t, name="ag")
    assert out.shape == (2 * n_workers,)
    assert torch.allclose(out, t.repeat(n_workers))


def test_broadcast_inplace(thvd):
    t = torch.randn(4)
    orig = t.clone()
    out = thvd.broadcast_(t, root_rank=0, name="bc")
    assert torch.allclose(out, orig)  # single-process: root value is ours


def test_compression_fp16_roundtrip(thvd, n_workers):
    t = torch.randn(8)
    out = thvd.allreduce(t, op=thvd.Sum, name="comp",
                         compression=thvd.Compression.fp16)
    assert out.dtype == torch.float32
    assert torch.allclose(out, t * n_workers, atol=2e-2)


# --- parameter / optimizer state broadcast ----------------------------------

def test_broadcast_parameters_state_dict(thvd):
    model = torch.nn.Linear(3, 2)
    before = {k: v.clone() for k, v in model.state_dict().items()}
    thvd.broadcast_parameters(model.state_dict(), root_rank=0)
    for k, v in model.state_dict().items():
        assert torch.allclose(v, before[k])


def test_broadcast_optimizer_state(thvd):
    model = torch.nn.Linear(3, 2)
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss = model(torch.randn(4, 3)).sum()
    loss.backward()
    opt.step()
    thvd.broadcast_optimizer_state(opt, root_rank=0)
    assert len(opt.state_dict()["state"]) > 0


# --- DistributedOptimizer ---------------------------------------------------

def test_distributed_optimizer_matches_plain_sgd(thvd):
    """On identical inputs (replicated across the virtual mesh) the
    distributed optimizer must match plain SGD exactly (averaging
    identical gradients is the identity)."""
    torch.manual_seed(7)
    X = torch.randn(16, 4)
    y = torch.randn(16, 1)

    def build():
        torch.manual_seed(1)
        return torch.nn.Linear(4, 1)

    ref = build()
    ref_opt = torch.optim.SGD(ref.parameters(), lr=0.05)
    dist = build()
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(dist.parameters(), lr=0.05),
        named_parameters=dist.named_parameters())

    for _ in range(3):
        for m, o in ((ref, ref_opt), (dist, opt)):
            o.zero_grad()
            torch.nn.functional.mse_loss(m(X), y).backward()
            o.step()
    for pr, pd in zip(ref.parameters(), dist.parameters()):
        assert torch.allclose(pr, pd, atol=1e-6), (pr, pd)


def test_distributed_optimizer_backward_passes_per_step(thvd):
    """Gradients accumulate locally for N passes, reduce on the Nth."""
    model = torch.nn.Linear(2, 1, bias=False)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1.0),
        named_parameters=model.named_parameters(),
        backward_passes_per_step=2)
    w0 = next(model.parameters()).detach().clone()
    X = torch.ones(1, 2)
    (model(X)).sum().backward()       # pass 1: no reduction submitted
    assert not opt._handles
    (model(X)).sum().backward()       # pass 2: reduction fires
    assert opt._handles
    opt.step()
    w1 = next(model.parameters()).detach()
    # grad of sum(w·x) over two passes = 2*x; averaged over workers = 2*x
    assert torch.allclose(w0 - w1, 2 * torch.ones(1, 2))


def test_distributed_optimizer_predivide(thvd):
    model = torch.nn.Linear(2, 1, bias=False)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1.0),
        named_parameters=model.named_parameters(),
        gradient_predivide_factor=2.0)
    w0 = next(model.parameters()).detach().clone()
    (model(torch.ones(1, 2))).sum().backward()
    opt.step()
    # pre/post scales cancel: net effect is still the plain average
    assert torch.allclose(w0 - next(model.parameters()).detach(),
                          torch.ones(1, 2))


def test_zero_grad_guard(thvd):
    model = torch.nn.Linear(2, 1)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    (model(torch.ones(1, 2))).sum().backward()
    with pytest.raises(AssertionError, match="in flight"):
        opt.zero_grad()
    opt.step()  # clears handles
    opt.zero_grad()


# --- real 2-process DP equivalence (reference: test_torch.py parallel) ------

def test_torch_two_process_training_matches_single():
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.torch_training_fn, np=2, env=env,
                  port=free_port())
    by_rank = {r["rank"]: r for r in results}
    # both processes end with identical params (same averaged gradients)
    for a, b in zip(by_rank[0]["params"], by_rank[1]["params"]):
        np.testing.assert_allclose(a, b, atol=1e-6)

    # single-process full-batch reference (DP on equal shards == full batch)
    torch.manual_seed(42)
    model = torch.nn.Sequential(
        torch.nn.Linear(4, 8), torch.nn.Tanh(), torch.nn.Linear(8, 1))
    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    y = (X @ rng.randn(4, 1)).astype(np.float32)
    Xt, yt = torch.from_numpy(X), torch.from_numpy(y)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    losses = []
    for _ in range(3):
        opt.zero_grad()
        loss = torch.nn.functional.mse_loss(model(Xt), yt)
        loss.backward()
        opt.step()
        losses.append(float(loss))
    np.testing.assert_allclose(by_rank[0]["losses"], losses, atol=1e-4)


# --- SyncBatchNorm (reference: horovod/torch/sync_batch_norm.py) ------------

def test_sync_batch_norm_matches_global_batch_bn(thvd, n_workers):
    """Sync BN over the mesh must equal plain BatchNorm over the GLOBAL
    batch (every virtual chip contributes a replica of the local batch —
    the reference's small-local/large-global equivalence)."""
    torch.manual_seed(0)
    x = torch.randn(6, 4, 5, 5)
    plain = torch.nn.BatchNorm2d(4, momentum=0.1)
    sync = thvd.SyncBatchNorm(4, momentum=0.1)
    sync.load_state_dict(plain.state_dict())
    sync.train(); plain.train()
    y_plain = plain(torch.cat([x] * n_workers))[:6]
    y_sync = sync(x)
    assert torch.allclose(y_sync, y_plain, atol=1e-5)
    assert torch.allclose(sync.running_mean, plain.running_mean, atol=1e-5)
    assert torch.allclose(sync.running_var, plain.running_var, atol=1e-5)


def test_sync_batch_norm_grads_match(thvd):
    torch.manual_seed(1)
    x1 = torch.randn(4, 3, 6, requires_grad=True)
    x2 = x1.detach().clone().requires_grad_(True)
    plain = torch.nn.BatchNorm1d(3)
    sync = thvd.SyncBatchNorm(3)
    sync.load_state_dict(plain.state_dict())
    plain.train(); sync.train()
    (plain(x1) ** 2).sum().backward()
    (sync(x2) ** 2).sum().backward()
    assert torch.allclose(x2.grad, x1.grad, atol=1e-4)
    assert torch.allclose(sync.weight.grad, plain.weight.grad, atol=1e-4)
    assert torch.allclose(sync.bias.grad, plain.bias.grad, atol=1e-4)


def test_sync_batch_norm_eval_mode(thvd):
    sync = thvd.SyncBatchNorm(2)
    sync.running_mean.fill_(1.0)
    sync.running_var.fill_(4.0)
    sync.eval()
    x = torch.ones(2, 2, 3)
    y = sync(x)
    want = (1.0 - 1.0) / np.sqrt(4.0 + sync.eps)
    assert torch.allclose(y, torch.full_like(y, want), atol=1e-6)


def test_sync_batch_norm_affine_false_and_fp16(thvd):
    sbn = thvd.SyncBatchNorm(3, affine=False)
    sbn.train()
    x = torch.randn(4, 3, 5, requires_grad=True)
    y = sbn(x)
    y.sum().backward()
    assert x.grad is not None
    # fp16 input keeps its dtype through the drop-in contract
    sbn16 = thvd.SyncBatchNorm(2)
    x16 = torch.randn(4, 2, 3).half()
    assert sbn16(x16).dtype == torch.float16


def test_grouped_allgather_and_reducescatter(thvd, n_workers):
    ts = [torch.ones(2) * (i + 1) for i in range(2)]
    outs = thvd.grouped_allgather(ts, name="gag")
    for i, o in enumerate(outs):
        assert o.shape == (2 * n_workers,)
        assert torch.allclose(o, torch.ones(2 * n_workers) * (i + 1))
    t = torch.arange(float(n_workers * 2))
    out = thvd.reducescatter(t, op=thvd.Sum, name="rs")
    # replicated input: reduction is x * n, this worker keeps slice 0
    assert out.shape == (2,)
    assert torch.allclose(out, t[:2] * n_workers)


# --- TorchState (reference: horovod/torch/elastic/state.py) -----------------

def test_torch_state_commit_restore(thvd):
    model = torch.nn.Linear(2, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    state = thvd.elastic.TorchState(model=model, optimizer=opt, epoch=3)
    w0 = model.weight.detach().clone()
    state.commit()
    # mutate everything, then roll back
    with torch.no_grad():
        model.weight.add_(1.0)
    (model(torch.ones(1, 2)).sum()).backward()
    opt.step()
    state.epoch = 9
    state.restore()
    assert torch.allclose(model.weight, w0)
    assert state.epoch == 3


def test_torch_state_sync_noop_single_process(thvd):
    model = torch.nn.Linear(2, 2)
    state = thvd.elastic.TorchState(model=model, step=5)
    state.sync()  # broadcast from self: values unchanged
    assert state.step == 5


def test_torch_state_run_wrapper_available(thvd):
    assert callable(thvd.elastic.run)
    assert thvd.elastic.ElasticSampler is not None


def test_torch_reducescatter_two_process():
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.torch_reducescatter_fn, np=2, env=env,
                  port=free_port())
    by_rank = {r["rank"]: r for r in results}
    # reduction: arange(4) * (1 + 2) = [0, 3, 6, 9]; rank0 keeps [0, 3],
    # rank1 keeps [6, 9]
    assert by_rank[0]["out"] == [0.0, 3.0]
    assert by_rank[1]["out"] == [6.0, 9.0]


def test_grouped_reducescatter(thvd, n_workers):
    """hvd.grouped_reducescatter parity: one atomic group, each tensor
    reduced then sliced to this worker's rows."""
    import torch
    a = torch.ones(n_workers * 2, 3)
    b = torch.full((n_workers, 1), 2.0)
    outs = thvd.grouped_reducescatter([a, b], op=thvd.Sum, name="grs")
    assert outs[0].shape == (2, 3)
    assert outs[1].shape == (1,) or outs[1].shape == (1, 1)
    assert float(outs[0][0, 0]) == float(n_workers)
    assert float(outs[1].reshape(-1)[0]) == 2.0 * n_workers


def test_allreduce_inplace_semantics(thvd, n_workers):
    """Reference: hvd.allreduce_ / allreduce_async_ modify the argument
    tensor in place (the former aliases returned fresh tensors)."""
    t = torch.ones(4)
    out = thvd.allreduce_(t, op=thvd.Sum, name="inplace_sum")
    assert out is t
    assert torch.allclose(t, torch.full((4,), float(n_workers)))

    t2 = torch.ones(3)
    h = thvd.allreduce_async_(t2, op=thvd.Sum, name="inplace_async")
    out2 = h.synchronize()
    assert out2 is t2
    assert torch.allclose(t2, torch.full((3,), float(n_workers)))


def test_grouped_allreduce_inplace(thvd, n_workers):
    ts = [torch.ones(2) * (i + 1) for i in range(3)]
    outs = thvd.grouped_allreduce_(ts, op=thvd.Sum, name="grp_inplace")
    for i, (t, o) in enumerate(zip(ts, outs)):
        assert o is t
        assert torch.allclose(t, torch.full((2,), float((i + 1) * n_workers)))

    ts2 = [torch.ones(2), torch.ones(2) * 2]
    h = thvd.grouped_allreduce_async_(ts2, op=thvd.Sum, name="grp_ia")
    outs2 = h.synchronize()
    for i, (t, o) in enumerate(zip(ts2, outs2)):
        assert o is t
        assert torch.allclose(t, torch.full((2,), float((i + 1) * n_workers)))


def test_reducescatter_async(thvd, n_workers):
    """hvd.reducescatter_async: handle resolves to this worker's dim-0
    slice of the reduction."""
    t = torch.arange(2.0 * n_workers).reshape(2 * n_workers, 1)
    h = thvd.reducescatter_async(t, op=thvd.Sum, name="rs_async")
    h.wait(10)
    out = h.synchronize()
    assert torch.allclose(out, t[:2] * n_workers)

    ts = [torch.ones((n_workers, 2)) * (i + 1) for i in range(2)]
    hg = thvd.grouped_reducescatter_async(ts, op=thvd.Sum, name="grs_a")
    assert hg.wait(10)
    outs = hg.synchronize()
    for i, o in enumerate(outs):
        assert torch.allclose(o, torch.full((1, 2),
                                            float((i + 1) * n_workers)))
