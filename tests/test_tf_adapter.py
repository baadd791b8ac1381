"""TensorFlow + Keras adapter tests.

Reference parity: ``test/parallel/test_tensorflow.py`` +
``test_tensorflow2_keras.py`` (SURVEY.md §4) — tape/optimizer wrappers,
broadcast_variables, callbacks — on the 8-device virtual mesh.  The
equivalence bar (VERDICT #3): a ``tf.function`` training loop through
``DistributedGradientTape`` matches the single-process loop exactly
(averaging identical replicated gradients is the identity).
"""

import numpy as np
import pytest

from _helpers import free_port

tf = pytest.importorskip("tensorflow")

import helpers_runner  # noqa: E402
from horovod_tpu.runner import run  # noqa: E402
import os  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_allreduce_eager(tfhvd, n_workers):
    t = tf.constant([1.0, 2.0, 3.0])
    out = tfhvd.allreduce(t, op=tfhvd.Sum, name="tf_sum")
    np.testing.assert_allclose(out.numpy(), t.numpy() * n_workers)
    out = tfhvd.allreduce(t, name="tf_avg")
    np.testing.assert_allclose(out.numpy(), t.numpy())


def test_allreduce_inside_tf_function(tfhvd, n_workers):
    @tf.function
    def fn(x):
        return tfhvd.allreduce(x, op=tfhvd.Sum, name="tf_fn_sum")

    out = fn(tf.ones((2, 2)))
    np.testing.assert_allclose(out.numpy(), np.full((2, 2), n_workers))


def test_grouped_allreduce(tfhvd, n_workers):
    ts = [tf.ones(2) * (i + 1) for i in range(3)]
    outs = tfhvd.grouped_allreduce(ts, op=tfhvd.Sum, name="tf_grp")
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(),
                                   np.full(2, (i + 1) * n_workers))


def test_allgather_broadcast(tfhvd, n_workers):
    t = tf.range(3, dtype=tf.float32)
    g = tfhvd.allgather(t, name="tf_ag")
    assert g.shape[0] == 3 * n_workers
    b = tfhvd.broadcast(t, root_rank=0, name="tf_bc")
    np.testing.assert_allclose(b.numpy(), t.numpy())


def test_broadcast_variables(tfhvd):
    v1 = tf.Variable([1.0, 2.0])
    v2 = tf.Variable([[3.0]])
    before = [v1.numpy().copy(), v2.numpy().copy()]
    tfhvd.broadcast_variables([v1, v2], root_rank=0)
    np.testing.assert_allclose(v1.numpy(), before[0])
    np.testing.assert_allclose(v2.numpy(), before[1])


def test_distributed_gradient_tape_matches_plain(tfhvd):
    """VERDICT #3 done-criterion: tf.function training matches the
    single-process loop (replicated inputs → averaged grads identical)."""
    w_ref = tf.Variable([[1.0], [2.0]])
    w_dist = tf.Variable([[1.0], [2.0]])
    X = tf.constant(np.random.RandomState(0).randn(8, 2).astype("f4"))
    y = tf.matmul(X, tf.constant([[0.5], [-1.0]]))

    def step_plain():
        with tf.GradientTape() as tape:
            loss = tf.reduce_mean((tf.matmul(X, w_ref) - y) ** 2)
        g = tape.gradient(loss, [w_ref])
        w_ref.assign_sub(0.1 * g[0])
        return loss

    @tf.function
    def step_dist():
        tape = tfhvd.DistributedGradientTape(tf.GradientTape())
        with tape:
            loss = tf.reduce_mean((tf.matmul(X, w_dist) - y) ** 2)
        g = tape.gradient(loss, [w_dist])
        w_dist.assign_sub(0.1 * g[0])
        return loss

    for _ in range(5):
        lp = step_plain()
        ld = step_dist()
        np.testing.assert_allclose(ld.numpy(), lp.numpy(), rtol=1e-5)
    np.testing.assert_allclose(w_dist.numpy(), w_ref.numpy(), rtol=1e-5)


def test_tape_backward_passes_per_step(tfhvd):
    w = tf.Variable(2.0)
    tape_w = tfhvd.DistributedGradientTape(backward_passes_per_step=2)
    with tape_w:
        loss = w * 3.0
    g1 = tape_w.gradient(loss, [w])
    assert float(g1[0]) == 0.0  # pass 1: accumulated, nothing reduced
    tape2 = tf.GradientTape()
    tape_w._wrapped = tape2
    with tape_w:
        loss = w * 3.0
    g2 = tape_w.gradient(loss, [w])
    assert float(g2[0]) == 6.0  # sum over the two passes, averaged over
    # identical workers


def test_distributed_optimizer_apply_gradients(tfhvd):
    opt = tf.keras.optimizers.SGD(learning_rate=1.0)
    opt = tfhvd.DistributedOptimizer(opt)
    v = tf.Variable([1.0, 1.0])
    opt.apply_gradients([(tf.constant([0.5, 0.5]), v)])
    np.testing.assert_allclose(v.numpy(), [0.5, 0.5])


# --- Keras callbacks --------------------------------------------------------

def _tiny_keras_model():
    m = tf.keras.Sequential([
        tf.keras.layers.Input(shape=(4,)),
        tf.keras.layers.Dense(3, activation="relu"),
        tf.keras.layers.Dense(1),
    ])
    m.compile(optimizer=tf.keras.optimizers.SGD(learning_rate=0.08),
              loss="mse")
    return m


def test_keras_fit_with_callbacks(tfhvd):
    import horovod_tpu.keras as khvd
    X = np.random.RandomState(1).randn(32, 4).astype("f4")
    y = X @ np.array([[1.0], [0.5], [-0.5], [0.2]], dtype="f4")
    model = _tiny_keras_model()
    bc = khvd.BroadcastGlobalVariablesCallback(root_rank=0)
    ma = khvd.MetricAverageCallback()
    wu = khvd.LearningRateWarmupCallback(initial_lr=0.08, warmup_epochs=2)
    hist = model.fit(X, y, epochs=3, batch_size=8, verbose=0,
                     callbacks=[bc, ma, wu])
    assert bc.broadcast_done
    losses = hist.history["loss"]
    assert losses[-1] < losses[0]
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr == pytest.approx(0.08, rel=1e-5)


def test_lr_warmup_ramps_from_scaled_down(tfhvd, n_workers):
    import horovod_tpu.keras as khvd
    model = _tiny_keras_model()
    wu = khvd.LearningRateWarmupCallback(initial_lr=0.8, warmup_epochs=4)
    wu.set_model(model)
    wu.on_epoch_begin(0)
    wu.on_train_batch_begin(0)
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr < 0.8  # still ramping
    assert lr >= 0.8 / n_workers
    wu.on_epoch_begin(3)
    wu.on_train_batch_begin(0)
    wu.on_epoch_end(3)
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr == pytest.approx(0.8, rel=1e-6)


def test_metric_average_callback_passthrough(tfhvd):
    import horovod_tpu.keras as khvd
    ma = khvd.MetricAverageCallback()
    logs = {"loss": 0.5, "acc": 0.75}
    ma.on_epoch_end(0, logs)
    # single-controller: metrics replicated → average is the identity
    assert logs["loss"] == pytest.approx(0.5)
    assert logs["acc"] == pytest.approx(0.75)


# --- real 2-process TF training equivalence ---------------------------------

def test_tf_two_process_tape_training_matches_single():
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.tf_training_fn, np=2, env=env, port=free_port())
    by_rank = {r["rank"]: r for r in results}
    np.testing.assert_allclose(by_rank[0]["w"], by_rank[1]["w"], atol=1e-6)
    # single-process full-batch reference
    X = np.random.RandomState(3).randn(8, 2).astype("f4")
    y = (X @ np.array([[1.0], [-0.5]], dtype="f4")).astype("f4")
    w = tf.Variable([[0.2], [0.1]])
    for _ in range(3):
        with tf.GradientTape() as tape:
            loss = tf.reduce_mean(
                (tf.matmul(tf.constant(X), w) - tf.constant(y)) ** 2)
        g = tape.gradient(loss, [w])
        w.assign_sub(0.5 * g[0])
    np.testing.assert_allclose(by_rank[0]["w"], w.numpy().tolist(),
                               atol=1e-5)


def test_jit_compile_singleprocess_collectives(tfhvd, n_workers):
    """VERDICT r3 #2 (reference: xla_mpi_ops.cc): single-process
    collectives lower to pure TF ops at trace time, so
    tf.function(jit_compile=True) compiles them natively — and the
    results match the engine's eager replicated semantics."""

    @tf.function(jit_compile=True)
    def step(x):
        a = tfhvd.allreduce(x, op=tfhvd.Sum)
        b = tfhvd.allreduce(x)                   # average: identity
        c = tfhvd.broadcast(x, 0)
        d = tfhvd.allgather(x)
        g = tfhvd.grouped_allreduce([x, 2.0 * x], op=tfhvd.Sum)
        return a, b, c, d, g

    x = tf.constant([[1.0, 2.0]])
    a, b, c, d, g = step(x)
    np.testing.assert_allclose(a.numpy(), x.numpy() * n_workers)
    np.testing.assert_allclose(b.numpy(), x.numpy())
    np.testing.assert_allclose(c.numpy(), x.numpy())
    assert d.shape == (n_workers, 2)
    np.testing.assert_allclose(g[1].numpy(), 2.0 * x.numpy() * n_workers)
    # identical to the engine's eager path
    eager = tfhvd.allreduce(x, op=tfhvd.Sum, name="jit_parity")
    np.testing.assert_allclose(a.numpy(), np.asarray(eager))


def test_jit_compile_multiprocess_error_is_actionable(tfhvd, monkeypatch):
    """With the custom-op bridge fenced off (HOROVOD_TF_XLA_OPS=0),
    multi-process collectives fall back to py_function and cannot live
    inside an XLA cluster; the compile error must NAME the fix instead
    of a bare EagerPyFunc (VERDICT r3 #2 'close or fence — documented
    failure mode').  With the bridge ON they compile — covered by
    test_tf_jit_compile_two_process."""
    monkeypatch.setattr(tfhvd, "cross_size", lambda: 2)
    monkeypatch.setenv("HOROVOD_TF_XLA_OPS", "0")

    @tf.function(jit_compile=True)
    def step(x):
        return tfhvd.allreduce(x, name="fence_t")

    with pytest.raises(Exception) as ei:
        step(tf.constant([1.0, 2.0]))
    assert "requires_jit_compile_False_see_docs_adapters_md" in str(ei.value)


def test_grouped_allgather(tfhvd, n_workers):
    """hvd.grouped_allgather parity: a list gathers as one fusion group,
    eagerly and under jit_compile (single-process trace-time lowering)."""
    a = tf.constant([[1.0, 2.0]])
    b = tf.constant([[3.0], [4.0]])
    outs = tfhvd.grouped_allgather([a, b], name="tf_gag")
    assert outs[0].shape == (n_workers, 2)
    assert outs[1].shape == (2 * n_workers, 1)
    np.testing.assert_allclose(outs[0].numpy()[0], [1.0, 2.0])

    @tf.function(jit_compile=True)
    def step(x, y):
        return tfhvd.grouped_allgather([x, y])

    ja, jb = step(a, b)
    np.testing.assert_allclose(ja.numpy(), outs[0].numpy())
    np.testing.assert_allclose(jb.numpy(), outs[1].numpy())


def test_graph_mode_topology_ops(tfhvd, n_workers):
    """rank_op/size_op/local_*_op parity (reference: graph-mode ops)."""

    @tf.function
    def f():
        return (tfhvd.rank_op(), tfhvd.size_op(),
                tfhvd.local_rank_op(), tfhvd.local_size_op())

    r, s, lr, ls = f()
    assert int(s) == n_workers
    assert int(r) == 0 and int(lr) == 0
    assert int(ls) == n_workers


def test_jit_compile_singleprocess_alltoall(tfhvd, n_workers):
    """ADVICE r4 #3: uniform/no-splits alltoall also lowers to pure TF
    ops at trace time in single-process jobs, so a
    tf.function(jit_compile=True) graph containing it compiles natively
    and matches the engine's eager replicated semantics."""

    x = tf.reshape(tf.range(2.0 * n_workers), (2 * n_workers, 1))

    @tf.function(jit_compile=True)
    def step_nosplits(t):
        return tfhvd.alltoall(t)

    @tf.function(jit_compile=True)
    def step_uniform(t):
        return tfhvd.alltoall(t, splits=[2] * n_workers)

    out = step_nosplits(x)
    eager = tfhvd.alltoall(x, name="jit_a2a_parity")
    np.testing.assert_allclose(out.numpy(), np.asarray(eager))
    out_u = step_uniform(x)
    eager_u = tfhvd.alltoall(x, splits=[2] * n_workers,
                             name="jit_a2a_parity_u")
    np.testing.assert_allclose(out_u.numpy(), np.asarray(eager_u))


def test_alltoall_splits_validation_mode_independent(tfhvd, n_workers):
    """Bad splits fail identically whether traced under jit_compile or
    run eagerly (the lowering must not bypass engine validation)."""
    x = tf.reshape(tf.range(2.0 * n_workers), (2 * n_workers, 1))

    with pytest.raises(ValueError, match="one entry per worker"):
        tfhvd.alltoall(x, splits=[2] * (n_workers + 1), name="bad_eager")

    @tf.function(jit_compile=True)
    def step(t):
        return tfhvd.alltoall(t, splits=[2] * (n_workers + 1))

    with pytest.raises(ValueError, match="one entry per worker"):
        step(x)

    # sum-mismatched uniform splits: engine chunks by dim0 // n; the
    # traced path must agree
    @tf.function(jit_compile=True)
    def step2(t):
        return tfhvd.alltoall(t, splits=[1] * n_workers)

    np.testing.assert_allclose(
        step2(x).numpy(),
        np.asarray(tfhvd.alltoall(x, splits=[1] * n_workers, name="sm")))


def test_grouped_allreduce_single_tensor_group(tfhvd):
    """A 1-member group must come back as a 1-list, not a bare tensor
    (the engine's single-output unwrap does not apply to groups): the
    tape/optimizer grouped-gradient path hits this with 1-variable
    models."""
    n = tfhvd.size()
    out = tfhvd.grouped_allreduce([tf.constant([2.0, 4.0])], op=tfhvd.Sum)
    assert isinstance(out, list) and len(out) == 1
    np.testing.assert_allclose(out[0].numpy(), [2.0 * n, 4.0 * n])
    ga = tfhvd.grouped_allgather([tf.constant([[1.0]])])
    assert isinstance(ga, list) and len(ga) == 1
    np.testing.assert_allclose(ga[0].numpy(), [[1.0]] * n)


def test_tape_gradient_compression_and_predivide_grouped(tfhvd):
    """The grouped tape path preserves compression + predivide
    semantics (fp16 wire, pre/postscale composition)."""
    v = tf.Variable([2.0, 6.0])
    tape = tfhvd.DistributedGradientTape(
        tf.GradientTape(), compression=tfhvd.Compression.fp16,
        gradient_predivide_factor=2.0)
    with tape:
        loss = tf.reduce_sum(v * v)
    g = tape.gradient(loss, [v])
    np.testing.assert_allclose(g[0].numpy(), [4.0, 12.0], rtol=1e-3)


def test_reducescatter_eager(tfhvd, n_workers):
    """Reference: hvd.tensorflow reducescatter — reduce across workers,
    keep this worker's dim-0 slice (torch adapter semantics mirrored)."""
    t = tf.reshape(tf.range(2.0 * n_workers), (2 * n_workers, 1))
    out = tfhvd.reducescatter(t, op=tfhvd.Sum, name="tf_rs_sum")
    # replicated contribution, worker 0's slice, scaled by n
    np.testing.assert_allclose(out.numpy(), t.numpy()[:2] * n_workers)
    avg = tfhvd.reducescatter(t, name="tf_rs_avg")
    np.testing.assert_allclose(avg.numpy(), t.numpy()[:2])


def test_grouped_reducescatter_eager(tfhvd, n_workers):
    ts = [tf.ones((n_workers, 2)) * (i + 1) for i in range(3)]
    outs = tfhvd.grouped_reducescatter(ts, op=tfhvd.Sum, name="tf_grs")
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.numpy(),
                                   np.full((1, 2), (i + 1) * n_workers))


def test_jit_compile_singleprocess_reducescatter(tfhvd, n_workers):
    """Single-process trace-time lowering to pure TF ops: a
    tf.function(jit_compile=True) graph containing reducescatter
    compiles natively and matches the eager engine path."""
    x = tf.reshape(tf.range(2.0 * n_workers), (2 * n_workers, 1))

    @tf.function(jit_compile=True)
    def step(t):
        return tfhvd.reducescatter(t, op=tfhvd.Sum)

    out = step(x)
    eager = tfhvd.reducescatter(x, op=tfhvd.Sum, name="jit_rs_parity")
    np.testing.assert_allclose(out.numpy(), np.asarray(eager))


def test_reducescatter_validation_mode_independent(tfhvd, n_workers):
    """Bad op / non-dividing dim-0 raise the same ValueError eagerly and
    at trace time (the engine's submission-time checks mirrored)."""
    bad_rows = tf.ones((2 * n_workers + 1, 1))
    with pytest.raises(ValueError, match="not divisible"):
        tfhvd.reducescatter(bad_rows, name="rs_bad_eager")

    @tf.function
    def step(t):
        return tfhvd.reducescatter(t, op=tfhvd.Adasum)

    with pytest.raises(ValueError, match="Sum and Average"):
        step(tf.ones((n_workers, 1)))


def test_lr_schedule_callback(tfhvd):
    """LearningRateScheduleCallback (reference: the staircase /
    exponential-decay half of the large-batch recipe): constant or
    callable multiplier over [start_epoch, end_epoch)."""
    import horovod_tpu.keras as khvd
    model = _tiny_keras_model()

    sc = khvd.LearningRateScheduleCallback(
        initial_lr=0.08, multiplier=lambda epoch: 0.1 ** (epoch // 2),
        start_epoch=2)
    sc.set_model(model)
    sc.on_epoch_begin(0)  # before start_epoch: untouched
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr == pytest.approx(0.08, rel=1e-6)
    sc.on_epoch_begin(2)
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr == pytest.approx(0.08 * 0.1, rel=1e-6)
    sc.on_epoch_begin(4)
    lr = float(np.asarray(model.optimizer.learning_rate))
    assert lr == pytest.approx(0.08 * 0.01, rel=1e-6)

    # constant multiplier + smooth (non-staircase) fractional epochs
    model2 = _tiny_keras_model()
    sm = khvd.LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=lambda e: 1.0 / (1.0 + e),
        staircase=False, steps_per_epoch=4)
    sm.set_model(model2)
    sm.on_epoch_begin(1)
    sm.on_train_batch_begin(0)   # epoch 1.0
    lr0 = float(np.asarray(model2.optimizer.learning_rate))
    assert lr0 == pytest.approx(0.5, rel=1e-6)
    sm.on_train_batch_begin(1)   # epoch 1.25
    lr1 = float(np.asarray(model2.optimizer.learning_rate))
    assert lr1 == pytest.approx(1.0 / 2.25, rel=1e-6)

    # constant (non-callable) multiplier path
    const = khvd.LearningRateScheduleCallback(initial_lr=0.5,
                                              multiplier=0.2)
    const.set_model(model2)
    const.on_epoch_begin(0)
    lr2 = float(np.asarray(model2.optimizer.learning_rate))
    assert lr2 == pytest.approx(0.1, rel=1e-6)


def test_tf_jit_compile_two_process():
    """THE xla_mpi_ops.cc capability: real 2-process collectives inside
    tf.function(jit_compile=True), lowered to XLA custom calls by the
    registered op bridge (closes VERDICT r4 Missing #3)."""
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.tf_jit_collectives_fn, np=3, env=env,
                  port=free_port())
    assert not any(r.get("skipped") for r in results), \
        "bridge must build on this image"
    by_rank = {r["rank"]: r for r in results}
    for r in (0, 1, 2):
        np.testing.assert_allclose(by_rank[r]["sum"], [6.0, 12.0])
        np.testing.assert_allclose(by_rank[r]["gathered"],
                                   [[1.0, 2.0], [2.0, 4.0], [3.0, 6.0]])
        np.testing.assert_allclose(by_rank[r]["grp0"], [6.0, 12.0])
        np.testing.assert_allclose(by_rank[r]["grp1"], [12.0, 24.0])
        np.testing.assert_allclose(by_rank[r]["bcast"], [1.0, 2.0])
    # process-set-scoped collective through the bridge attr path: the
    # spanning subset {0, 1} sums only its members' tensors
    np.testing.assert_allclose(by_rank[0]["ps_sum"], [3.0, 6.0])
    np.testing.assert_allclose(by_rank[1]["ps_sum"], [3.0, 6.0])


def test_tf_jit_compile_two_process_training_matches_single():
    """End-to-end DP training with the full step under jit_compile=True
    across 2 real processes equals the single-process full-batch run
    (the same equivalence bar as the non-jit tape test)."""
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.tf_jit_training_fn, np=2, env=env,
                  port=free_port())
    assert not any(r.get("skipped") for r in results)
    by_rank = {r["rank"]: r for r in results}
    np.testing.assert_allclose(by_rank[0]["w"], by_rank[1]["w"], atol=1e-6)
    X = np.random.RandomState(3).randn(8, 2).astype("f4")
    y = (X @ np.array([[1.0], [-0.5]], dtype="f4")).astype("f4")
    w = tf.Variable([[0.2], [0.1]])
    for _ in range(3):
        with tf.GradientTape() as tape:
            loss = tf.reduce_mean(
                (tf.matmul(tf.constant(X), w) - tf.constant(y)) ** 2)
        g = tape.gradient(loss, [w])
        w.assign_sub(0.5 * g[0])
    np.testing.assert_allclose(by_rank[0]["w"], w.numpy().tolist(),
                               atol=1e-5)


def test_sparse_allreduce_indexed_slices(tfhvd, n_workers):
    """hvd.allreduce on tf.IndexedSlices: allgather-based sparse
    reduction (reference: hvd.tensorflow's IndexedSlices handling) —
    duplicate indices sum when applied; Average divides by workers."""
    sl = tf.IndexedSlices(values=tf.constant([[1.0, 2.0], [3.0, 4.0]]),
                          indices=tf.constant([0, 2], dtype=tf.int64),
                          dense_shape=tf.constant([4, 2], dtype=tf.int64))
    out = tfhvd.allreduce(sl, op=tfhvd.Sum, name="sp_sum")
    assert isinstance(out, tf.IndexedSlices)
    assert out.values.shape[0] == 2 * n_workers
    dense = tf.scatter_nd(tf.reshape(out.indices, (-1, 1)), out.values,
                          (4, 2))
    np.testing.assert_allclose(
        dense.numpy(),
        np.array([[1, 2], [0, 0], [3, 4], [0, 0]], "f4") * n_workers)

    avg = tfhvd.allreduce(sl, name="sp_avg")  # Average
    dense_avg = tf.scatter_nd(tf.reshape(avg.indices, (-1, 1)),
                              avg.values, (4, 2))
    np.testing.assert_allclose(
        dense_avg.numpy(),
        np.array([[1, 2], [0, 0], [3, 4], [0, 0]], "f4"))


def test_tape_sparse_gradients(tfhvd, n_workers):
    """DistributedGradientTape keeps embedding gradients sparse by
    default (sparse_as_dense=False) and densifies on request."""
    emb = tf.Variable(tf.ones((5, 3)))

    def run_tape(sparse_as_dense):
        tape = tfhvd.DistributedGradientTape(
            tf.GradientTape(), sparse_as_dense=sparse_as_dense)
        with tape:
            rows = tf.nn.embedding_lookup(emb, tf.constant([1, 3]))
            loss = tf.reduce_sum(rows)
        return tape.gradient(loss, [emb])[0]

    g_sparse = run_tape(False)
    assert isinstance(g_sparse, tf.IndexedSlices)
    dense_from_sparse = tf.scatter_nd(
        tf.reshape(g_sparse.indices, (-1, 1)), g_sparse.values, (5, 3))
    g_dense = run_tape(True)
    assert not isinstance(g_dense, tf.IndexedSlices)
    # identical effective gradient either way (average of replicated
    # contributions; sparse applies n_workers copies divided by n)
    np.testing.assert_allclose(dense_from_sparse.numpy(), g_dense.numpy())


def test_tf_sparse_allreduce_two_process_ragged():
    """Real 2-process sparse allreduce with ragged per-rank nnz (the
    values/indices gathers ride Allgatherv)."""
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.tf_sparse_allreduce_fn, np=2, env=env,
                  port=free_port())
    for r in results:
        # rank0 contributes rows {0:1, 1:2}, rank1 {1:10} -> summed
        np.testing.assert_allclose(r["dense"], [1.0, 12.0, 0.0, 0.0])


def test_tf_keras_elastic_state(tfhvd):
    """TensorFlowKerasState (reference: horovod/tensorflow/elastic.py):
    commit/restore round-trips model+optimizer weights and scalars;
    sync broadcasts and re-saves."""
    from horovod_tpu.tensorflow.elastic import TensorFlowKerasState

    model = _tiny_keras_model()
    X = np.random.RandomState(0).randn(8, 4).astype("f4")
    y = X @ np.array([[1.0], [0.5], [-0.5], [0.2]], dtype="f4")
    model.train_on_batch(X, y)  # materialize optimizer slots

    state = TensorFlowKerasState(model, epoch=3, batch=7)
    w0 = [w.copy() for w in model.get_weights()]

    model.train_on_batch(X, y)  # perturb
    state.epoch = 5
    assert any(not np.allclose(a, b)
               for a, b in zip(w0, model.get_weights()))

    state.restore()
    for a, b in zip(w0, model.get_weights()):
        np.testing.assert_allclose(a, b)
    assert state.epoch == 3 and state.batch == 7

    # commit() captures the new point; restore returns to IT afterwards
    model.train_on_batch(X, y)
    state.epoch = 9
    state.commit()
    w1 = [w.copy() for w in model.get_weights()]
    model.train_on_batch(X, y)
    state.restore()
    for a, b in zip(w1, model.get_weights()):
        np.testing.assert_allclose(a, b)
    assert state.epoch == 9

    state.sync()  # replicated single-controller: broadcast is identity
    for a, b in zip(w1, model.get_weights()):
        np.testing.assert_allclose(a, b)


def test_distributed_optimizer_backward_passes_per_step(tfhvd):
    """DistributedOptimizer(backward_passes_per_step=N) accumulates N
    calls locally and reduces+applies on the N-th (reference: the TF
    LocalGradientAggregationHelper semantics)."""
    opt = tfhvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(learning_rate=1.0),
        backward_passes_per_step=2)
    v = tf.Variable([1.0, 1.0])

    applied = opt.apply_gradients([(tf.constant([0.25, 0.25]), v)])
    assert not bool(applied)  # pass 1: accumulated only
    np.testing.assert_allclose(v.numpy(), [1.0, 1.0])

    applied = opt.apply_gradients([(tf.constant([0.25, 0.25]), v)])
    assert bool(applied)  # pass 2: sum of both passes applied
    np.testing.assert_allclose(v.numpy(), [0.5, 0.5])

    # next cycle starts from zeroed accumulators
    opt.apply_gradients([(tf.constant([0.5, 0.5]), v)])
    np.testing.assert_allclose(v.numpy(), [0.5, 0.5])
    opt.apply_gradients([(tf.constant([0.5, 0.5]), v)])
    np.testing.assert_allclose(v.numpy(), [-0.5, -0.5])
