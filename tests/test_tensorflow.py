"""TensorFlow/Keras binding tests (reference analogue:
test/parallel/test_tensorflow.py + test_keras.py, SURVEY §4): single-process
semantics plus real multi-process workers over localhost TCP."""

import os

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")
keras = pytest.importorskip("keras")

import horovod_tpu.tensorflow as hvd_tf  # noqa: E402
import horovod_tpu.keras as hvd_keras  # noqa: E402
from test_native_core import _run_world  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "tf_worker.py")


@pytest.fixture(autouse=True)
def _tf_state_isolation():
    """Order-independence guard for the tf.function tests.

    ``tf.function`` tracing depends on process-global state that earlier
    tier-1 tests can leak: ``tf.config.run_functions_eagerly`` toggles
    (keras fits flip it), a dangling default FuncGraph from a test that
    died inside a ``graph.as_default()`` context, and the per-function
    autograph conversion allowlist — the source of the pre-PR-5
    order-dependent ``test_allreduce_in_tf_function`` flake, which never
    reproduced in isolation. Pin the state before every test in this
    module and restore the caller's afterwards.
    """
    was_eager_fns = tf.config.functions_run_eagerly()
    tf.config.run_functions_eagerly(False)
    # A leaked graph-mode default context would silently reroute every
    # hvd_tf op through the graph path — fail loudly instead, naming the
    # leak, rather than flaking on whatever that path returns.
    assert tf.executing_eagerly(), (
        "a previous test left a graph context as default; tf.function "
        "tests cannot run order-independently")
    yield
    tf.config.run_functions_eagerly(was_eager_fns)


class TestOpsSingleProcess:
    def test_allreduce_identity(self):
        t = tf.range(6, dtype=tf.float32)
        assert np.allclose(hvd_tf.allreduce(t).numpy(), t.numpy())

    def test_allreduce_scaling(self):
        out = hvd_tf.allreduce(tf.ones([4]), op=hvd_tf.Sum,
                               prescale_factor=3.0)
        assert np.allclose(out.numpy(), 3.0)

    def test_allreduce_grad(self):
        x = tf.Variable(tf.ones([3]))
        with tf.GradientTape() as tape:
            y = tf.reduce_sum(hvd_tf.allreduce(x))
        g = tape.gradient(y, x)
        assert np.allclose(g.numpy(), 1.0)

    def test_allreduce_in_tf_function(self):
        # autograph=False: the body is pure TF ops (no python control
        # flow), so the autograph source-conversion machinery — whose
        # per-process caches made this test order-dependent — has nothing
        # to contribute and is excluded outright; _tf_state_isolation
        # guards the rest of the global tracing state.
        @tf.function(autograph=False)
        def f(t):
            return hvd_tf.allreduce(t, op=hvd_tf.Sum)

        assert np.allclose(f(tf.ones([4])).numpy(), 1.0)

    def test_average_op_conflict(self):
        with pytest.raises(ValueError):
            hvd_tf.allreduce(tf.ones([2]), average=True, op=hvd_tf.Sum)

    def test_allgather_identity(self):
        t = tf.random.normal([3, 2])
        assert np.allclose(hvd_tf.allgather(t).numpy(), t.numpy())

    def test_broadcast_identity(self):
        t = tf.random.normal([4])
        assert np.allclose(hvd_tf.broadcast(t, 0).numpy(), t.numpy())

    def test_alltoall_identity(self):
        t = tf.range(4, dtype=tf.float32)
        out, splits = hvd_tf.alltoall(t)
        assert np.allclose(out.numpy(), t.numpy())
        assert list(splits.numpy()) == [4]

    def test_broadcast_variables(self):
        v = tf.Variable(tf.ones([3]))
        hvd_tf.broadcast_variables([v], root_rank=0)
        assert np.allclose(v.numpy(), 1.0)

    def test_broadcast_object(self):
        assert hvd_tf.broadcast_object({"a": 1}) == {"a": 1}

    def test_allgather_object(self):
        assert hvd_tf.allgather_object(7) == [7]

    def test_join(self):
        assert hvd_tf.join() == 0

    def test_compression_fp16(self):
        from horovod_tpu.tensorflow.compression import Compression

        t = tf.random.normal([8])
        c, ctx = Compression.fp16.compress(t)
        assert c.dtype == tf.float16
        d = Compression.fp16.decompress(c, ctx)
        assert d.dtype == tf.float32


class TestDistributedGradientTape:
    def test_wraps_and_computes(self):
        w = tf.Variable(tf.ones([3, 1]))
        x = tf.ones([2, 3])
        with hvd_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_sum(tf.matmul(x, w))
        (g,) = tape.gradient(loss, [w])
        assert np.allclose(g.numpy(), 2.0)

    def test_sparse_indexedslices(self):
        emb = tf.Variable(tf.random.normal([10, 4]))
        with hvd_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            rows = tf.gather(emb, [1, 3])
            loss = tf.reduce_sum(rows)
        (g,) = tape.gradient(loss, [emb])
        assert isinstance(g, tf.IndexedSlices)
        assert g.values.shape[0] == 2

    def test_sparse_average_scales_by_size(self, monkeypatch):
        """Average must divide gathered sparse values by the world the
        allgather spanned — the PROCESS world, not size()'s device world
        (reference tensorflow/__init__.py:107; ADVICE r1 + the r5
        sparse_as_dense agreement test exposing the divisor mismatch)."""
        from horovod_tpu.ops import collective_ops as C

        monkeypatch.setattr(C, "_eager_world", lambda: 4)
        emb = tf.Variable(tf.ones([10, 4]))
        with hvd_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            rows = tf.gather(emb, [1, 3])
            loss = tf.reduce_sum(rows)
        (g,) = tape.gradient(loss, [emb])
        # world-1 allgather is identity, so values = raw/4.
        assert np.allclose(g.values.numpy(), 0.25)

    def test_sparse_as_dense_densifies(self):
        """sparse_as_dense=True turns the IndexedSlices gradient into a
        dense tensor before reduction, numerically equal to the
        densified gather-path result (reference
        tensorflow/__init__.py:260,299,437; the 2-process agreement leg
        lives in tests/tf_worker.py)."""
        emb = tf.Variable(tf.ones([6, 3]))

        def grad(sparse_as_dense):
            with hvd_tf.DistributedGradientTape(
                    tf.GradientTape(),
                    sparse_as_dense=sparse_as_dense) as tape:
                rows = tf.gather(emb, [1, 3, 1])  # duplicate index
                loss = tf.reduce_sum(rows * rows)
            (g,) = tape.gradient(loss, [emb])
            return g


        g_dense = grad(True)
        assert not isinstance(g_dense, tf.IndexedSlices)
        g_gather = grad(False)
        assert isinstance(g_gather, tf.IndexedSlices)
        np.testing.assert_allclose(
            g_dense.numpy(), tf.convert_to_tensor(g_gather).numpy(),
            rtol=1e-6)
        # row 1 hit twice -> 2*2*1, row 3 once -> 2*1.
        assert np.allclose(g_dense.numpy()[1], 4.0)
        assert np.allclose(g_dense.numpy()[3], 2.0)

    def test_sparse_adasum_rejected(self):
        emb = tf.Variable(tf.ones([10, 4]))
        with pytest.raises(NotImplementedError):
            with hvd_tf.DistributedGradientTape(
                    tf.GradientTape(), op=hvd_tf.Adasum) as tape:
                rows = tf.gather(emb, [1, 3])
                loss = tf.reduce_sum(rows)
            tape.gradient(loss, [emb])


class TestSyncBatchNorm:
    def test_matches_stock_bn_world1(self, monkeypatch):
        """World-1 allreduce is identity, so the synchronized path must
        reproduce the stock layer's training output exactly (forced onto
        the sync path by faking size=2)."""
        from horovod_tpu.tensorflow import sync_batch_norm as sbn_mod

        monkeypatch.setattr(sbn_mod, "size", lambda: 2)
        rs = np.random.RandomState(0)
        x = tf.constant(rs.randn(8, 5).astype(np.float32))
        sbn = hvd_tf.SyncBatchNormalization(momentum=0.9, epsilon=1e-3)
        ref = keras.layers.BatchNormalization(momentum=0.9, epsilon=1e-3)
        sbn.build(x.shape)
        ref.build(x.shape)
        out = sbn(x, training=True)
        expect = ref(x, training=True)
        assert np.allclose(out.numpy(), expect.numpy(), atol=1e-5)
        assert np.allclose(np.asarray(sbn.moving_mean),
                           np.asarray(ref.moving_mean), atol=1e-5)
        assert np.allclose(np.asarray(sbn.moving_variance),
                           np.asarray(ref.moving_variance), atol=1e-5)

    def test_inference_uses_moving_stats(self):
        x = tf.constant(np.random.RandomState(1).randn(4, 3)
                        .astype(np.float32))
        sbn = hvd_tf.SyncBatchNormalization()
        out = sbn(x, training=False)
        # moving stats are identity at init: output ~= x (eps shift only)
        assert np.allclose(out.numpy(), x.numpy(), atol=1e-2)


class TestTensorFlowState:
    def test_save_restore_sync_world1(self):
        v = tf.Variable([1.0, 2.0])
        st = hvd_tf.elastic.TensorFlowState(variables=[v], epoch=3)
        v.assign([9.0, 9.0])
        st.epoch = 7
        st.restore()
        assert np.allclose(v.numpy(), [1.0, 2.0])
        assert st.epoch == 3
        v.assign([5.0, 5.0])
        st.epoch = 4
        st.save()
        st.sync()  # world 1: broadcast is identity
        assert np.allclose(v.numpy(), [5.0, 5.0])
        assert st.epoch == 4

    def test_keras_state_wraps_model(self):
        model = keras.Sequential([keras.layers.Input(shape=(2,)),
                                  keras.layers.Dense(1)])
        st = hvd_tf.elastic.TensorFlowKerasState(model, epoch=0)
        w0 = [w.copy() for w in model.get_weights()]
        model.set_weights([w + 1.0 for w in w0])
        st.restore()
        for a, b in zip(model.get_weights(), w0):
            assert np.allclose(a, b)


class TestKerasOptimizer:
    def test_wraps_class_and_trains(self):
        keras.utils.set_random_seed(0)
        model = keras.Sequential([
            keras.layers.Input(shape=(4,)),
            keras.layers.Dense(8, activation="tanh"),
            keras.layers.Dense(1),
        ])
        opt = hvd_keras.DistributedOptimizer(
            keras.optimizers.SGD(learning_rate=0.1))
        assert isinstance(opt, keras.optimizers.SGD)
        model.compile(optimizer=opt, loss="mse")
        xs = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        ys = xs.sum(axis=1, keepdims=True).astype(np.float32)
        hist = model.fit(xs, ys, batch_size=16, epochs=3, verbose=0)
        assert hist.history["loss"][-1] < hist.history["loss"][0]

    def test_serialization_roundtrip(self):
        opt = hvd_keras.DistributedOptimizer(
            keras.optimizers.Adam(learning_rate=3e-4))
        cfg = opt.get_config()
        assert abs(cfg["learning_rate"] - 3e-4) < 1e-9


class TestKerasCallbacks:
    def _model(self):
        keras.utils.set_random_seed(0)
        model = keras.Sequential([
            keras.layers.Input(shape=(2,)),
            keras.layers.Dense(1),
        ])
        model.compile(optimizer=keras.optimizers.SGD(learning_rate=0.01),
                      loss="mse")
        return model

    def test_broadcast_callback_world1(self):
        model = self._model()
        xs = np.random.randn(8, 2).astype(np.float32)
        ys = np.zeros((8, 1), np.float32)
        model.fit(xs, ys, epochs=1, verbose=0, callbacks=[
            hvd_keras.callbacks.BroadcastGlobalVariablesCallback(0)])

    def test_metric_average_world1(self):
        model = self._model()
        xs = np.random.randn(8, 2).astype(np.float32)
        ys = np.zeros((8, 1), np.float32)
        model.fit(xs, ys, epochs=1, verbose=0, callbacks=[
            hvd_keras.callbacks.MetricAverageCallback()])

    def test_warmup_semantics_size4(self, monkeypatch):
        """Reference semantics (_keras/callbacks.py:139-143): warm from
        initial_lr/size up to initial_lr (the size-scaled LR the user set)."""
        model = self._model()
        cb = hvd_keras.callbacks.LearningRateWarmupCallback(
            initial_lr=0.4, warmup_epochs=2, steps_per_epoch=4)
        monkeypatch.setattr(cb, "_size", lambda: 4)
        cb.set_model(model)
        cb.on_epoch_begin(0)
        cb.on_train_batch_begin(0)
        lr0 = float(np.asarray(model.optimizer.learning_rate))
        cb.on_epoch_begin(1)
        cb.on_train_batch_begin(4)  # progress = (1 + 4/4)/2 = 1.0
        lr1 = float(np.asarray(model.optimizer.learning_rate))
        assert lr0 == pytest.approx(0.4 / 4)
        assert lr1 == pytest.approx(0.4)

    def test_warmup_reaches_target(self):
        model = self._model()
        cb = hvd_keras.callbacks.LearningRateWarmupCallback(
            initial_lr=0.01, warmup_epochs=2, steps_per_epoch=4)
        cb.set_model(model)
        cb.on_epoch_begin(0)
        cb.on_train_batch_begin(0)
        lr0 = float(np.asarray(model.optimizer.learning_rate))
        cb.on_epoch_begin(1)
        cb.on_train_batch_begin(3)
        lr1 = float(np.asarray(model.optimizer.learning_rate))
        # world of one: multiplier stays 1.0 throughout
        assert lr0 == pytest.approx(0.01)
        assert lr1 == pytest.approx(0.01)

    def test_schedule_staircase(self):
        model = self._model()
        cb = hvd_keras.callbacks.LearningRateScheduleCallback(
            initial_lr=0.1, multiplier=lambda e: 0.1 ** e, start_epoch=0)
        cb.set_model(model)
        cb.on_epoch_begin(0)
        assert float(np.asarray(
            model.optimizer.learning_rate)) == pytest.approx(0.1)
        cb.on_epoch_begin(2)
        assert float(np.asarray(
            model.optimizer.learning_rate)) == pytest.approx(0.001)


class TestKerasElastic:
    def test_state_save_restore(self):
        model = self._make()
        state = hvd_keras.elastic.KerasState(model, epoch=3)
        w0 = [np.copy(w) for w in model.get_weights()]
        model.set_weights([w * 0 + 99.0 for w in model.get_weights()])
        state.epoch = 7
        state.restore()
        for a, b in zip(model.get_weights(), w0):
            assert np.allclose(a, b)
        assert state.epoch == 3

    @staticmethod
    def _make():
        keras.utils.set_random_seed(0)
        model = keras.Sequential([
            keras.layers.Input(shape=(2,)),
            keras.layers.Dense(1),
        ])
        model.compile(optimizer="sgd", loss="mse")
        return model


class TestMXNetGate:
    def test_informative_import_error(self):
        with pytest.raises(ImportError, match="mxnet"):
            import horovod_tpu.mxnet  # noqa: F401


class TestMultiProcess:
    def test_world_2(self):
        _run_world(2, timeout=420, worker=WORKER)
