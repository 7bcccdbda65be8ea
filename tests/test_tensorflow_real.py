"""TF-binding tests against REAL TensorFlow/Keras objects (tf 2.21 /
Keras 3 are present in this image; these complement the numpy-fake suite
in test_tensorflow.py and exercise actual tf.Tensor / tf.GradientTape /
keras optimizer round trips — the reference's test_tensorflow.py
territory)."""

import numpy as np
import pytest


@pytest.fixture(scope="module", autouse=True)
def _tensorflow():
    """Imported by the worker that runs this file, not by all six at
    collection (8 to 13 s each)."""
    global tf, hvt_tf
    tf = pytest.importorskip("tensorflow")
    import horovod_tpu.tensorflow as hvt_tf


def test_allreduce_real_tensor_roundtrip():
    x = tf.constant([[1.0, 2.0], [3.0, 4.0]])
    out = hvt_tf.allreduce(x, name="real.t", average=True)
    assert isinstance(out, tf.Tensor)
    np.testing.assert_allclose(out.numpy(), x.numpy())  # 1 process: avg=id
    s = hvt_tf.allreduce(x, name="real.s", average=False,
                         prescale_factor=2.0)
    np.testing.assert_allclose(s.numpy(), 2 * x.numpy())


def test_allgather_broadcast_real_tensors():
    g = hvt_tf.allgather(tf.constant([[1.0, 2.0]]), name="real.g")
    np.testing.assert_allclose(g.numpy(), [[1.0, 2.0]])
    b = hvt_tf.broadcast(tf.constant([5, 6]), root_rank=0, name="real.b")
    assert b.numpy().tolist() == [5, 6]


def test_distributed_gradient_tape_real():
    w = tf.Variable([1.0, 2.0, 3.0])
    with hvt_tf.DistributedGradientTape(tf.GradientTape()) as tape:
        loss = tf.reduce_sum(w * w)
    (grad,) = tape.gradient(loss, [w])
    np.testing.assert_allclose(np.asarray(grad), 2 * w.numpy())


def test_distributed_gradient_tape_sparse_real():
    emb = tf.Variable(tf.ones((4, 3)))
    with hvt_tf.DistributedGradientTape(tf.GradientTape()) as tape:
        rows = tf.gather(emb, [1, 3])
        loss = tf.reduce_sum(rows) * 2.0
    (grad,) = tape.gradient(loss, [emb])
    assert isinstance(grad, tf.IndexedSlices)
    np.testing.assert_array_equal(np.sort(np.asarray(grad.indices)), [1, 3])
    np.testing.assert_allclose(np.asarray(grad.values), 2.0)


def test_distributed_optimizer_real_keras_training():
    """Custom loop with a real keras optimizer wrapped by the TF
    DistributedOptimizer converges (single process: reduction is
    identity, the wrapper plumbing is what is under test)."""
    rs = np.random.RandomState(0)
    W_true = rs.randn(4, 1).astype(np.float32)
    X = rs.randn(256, 4).astype(np.float32)
    y = X @ W_true

    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(1, use_bias=False, input_shape=(4,))])
    opt = hvt_tf.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    losses = []
    for _ in range(100):
        with tf.GradientTape() as tape:
            pred = model(X, training=True)
            loss = tf.reduce_mean((pred - y) ** 2)
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 1e-3, (losses[0], losses[-1])


def test_adasum_delta_optimizer_single_process_is_local_step():
    """op=Adasum returns the delta optimizer (reference
    tensorflow/__init__.py:471-567); with one process the combine is a
    no-op and the result must be EXACTLY the wrapped optimizer's local
    update (momentum statistics intact)."""
    from horovod_tpu.tensorflow import _DistributedAdasumOptimizer

    v = tf.Variable([1.0, 2.0, 3.0])
    v_ref = tf.Variable([1.0, 2.0, 3.0])
    opt = hvt_tf.DistributedOptimizer(
        tf.keras.optimizers.SGD(0.5, momentum=0.9), op=hvt_tf.Adasum)
    assert isinstance(opt, _DistributedAdasumOptimizer)
    ref = tf.keras.optimizers.SGD(0.5, momentum=0.9)
    for _ in range(3):
        g = tf.constant([0.1, -0.2, 0.3])
        opt.apply_gradients([(g, v)])
        ref.apply_gradients([(g, v_ref)])
    np.testing.assert_allclose(v.numpy(), v_ref.numpy(), rtol=1e-6)


def test_adasum_delta_optimizer_aggregation_and_guards():
    v = tf.Variable([0.0, 0.0])
    opt = hvt_tf.DistributedOptimizer(tf.keras.optimizers.SGD(1.0),
                                      op=hvt_tf.Adasum,
                                      backward_passes_per_step=2)
    g = tf.constant([1.0, 2.0])
    assert opt.apply_gradients([(g, v)]) is None       # aggregate only
    np.testing.assert_allclose(v.numpy(), 0.0)          # no update yet
    opt.apply_gradients([(g, v)])
    np.testing.assert_allclose(v.numpy(), [-2.0, -4.0])  # summed grads

    with pytest.raises(ValueError, match="process_set"):
        from horovod_tpu.ops.collective_ops import global_process_set
        ps = type(global_process_set)([0])
        hvt_tf.DistributedOptimizer(tf.keras.optimizers.SGD(1.0),
                                    op=hvt_tf.Adasum, process_set=ps)
    with pytest.raises(ValueError, match="prescale"):
        hvt_tf.DistributedOptimizer(tf.keras.optimizers.SGD(1.0),
                                    op=hvt_tf.Adasum, prescale_factor=2.0)


def test_distributed_optimizer_aggregation_with_real_optimizer():
    v = tf.Variable([0.0, 0.0])
    opt = hvt_tf.DistributedOptimizer(tf.keras.optimizers.SGD(1.0),
                                      backward_passes_per_step=2)
    g = tf.constant([1.0, 2.0])
    assert opt.apply_gradients([(g, v)]) is None       # aggregate only
    np.testing.assert_allclose(v.numpy(), 0.0)          # no update yet
    opt.apply_gradients([(g, v)])
    np.testing.assert_allclose(v.numpy(), [-2.0, -4.0])  # sum of 2 passes


def test_broadcast_variables_real_model():
    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(2, input_shape=(3,))])
    before = [w.numpy().copy() for w in model.weights]
    hvt_tf.broadcast_variables(model.weights, root_rank=0)
    for b, w in zip(before, model.weights):
        np.testing.assert_allclose(w.numpy(), b)  # 1 process: unchanged


def test_keras_lr_warmup_callback_real_fit():
    """keras.LearningRateWarmupCallback drives the real optimizer's lr
    through model.fit."""
    import horovod_tpu.keras as hvt_keras

    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(1, input_shape=(2,))])
    model.compile(optimizer=tf.keras.optimizers.SGD(0.4), loss="mse")
    cb = hvt_keras.LearningRateWarmupCallback(initial_lr=0.4,
                                              warmup_epochs=4)
    X = np.random.RandomState(0).randn(32, 2).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    seen = []

    class Probe(tf.keras.callbacks.Callback):
        def on_epoch_begin(self, epoch, logs=None):
            seen.append(float(model.optimizer.learning_rate))

    model.fit(X, y, epochs=4, batch_size=16, verbose=0,
              callbacks=[cb, Probe()])
    # warmup ramps from lr/size toward lr over warmup_epochs (size is
    # the session world size — 8 virtual chips in the test harness)
    import horovod_tpu as hvt

    n = hvt.size()
    expect = [0.4 / n * (e * (n - 1) / 4 + 1) for e in range(4)]
    assert len(seen) == 4
    np.testing.assert_allclose(seen, expect, rtol=1e-6)


def test_keras_broadcast_global_variables_real_model():
    import horovod_tpu.keras as hvt_keras

    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(2, input_shape=(3,))])
    before = [w.numpy().copy() for w in model.weights]
    # Keras 3: pass the model (the legacy global registry is gone)
    hvt_keras.broadcast_global_variables(0, model=model)
    for b, w in zip(before, model.weights):
        np.testing.assert_allclose(w.numpy(), b)
    if not hasattr(tf.keras.backend, "_get_variables"):
        # no model/variables and no legacy registry → actionable error
        with pytest.raises(ValueError, match="model"):
            hvt_keras.broadcast_global_variables(0)


def test_keras_lr_warmup_with_steps_per_epoch_ramps():
    """Regression: with steps_per_epoch (non-staircase path) the adapter
    must evaluate the schedule at each epoch's first step, not step 0 —
    the LR has to RAMP, not freeze at initial_lr/size."""
    import horovod_tpu.keras as hvt_keras

    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(1, input_shape=(2,))])
    model.compile(optimizer=tf.keras.optimizers.SGD(0.4), loss="mse")
    cb = hvt_keras.LearningRateWarmupCallback(
        initial_lr=0.4, warmup_epochs=4, steps_per_epoch=2)
    X = np.random.RandomState(0).randn(32, 2).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    seen = []

    class Probe(tf.keras.callbacks.Callback):
        def on_epoch_begin(self, epoch, logs=None):
            seen.append(float(model.optimizer.learning_rate))

    model.fit(X, y, epochs=4, batch_size=16, verbose=0,
              callbacks=[cb, Probe()])
    assert len(seen) == 4
    assert seen[-1] > seen[0], seen  # ramping, not frozen
    import horovod_tpu as hvt

    n = hvt.size()
    expect = [0.4 / n * (e * (n - 1) / 4 + 1) for e in range(4)]
    np.testing.assert_allclose(seen, expect, rtol=1e-6)


def test_sync_batch_norm_single_process_matches_plain_bn():
    """Single process: SyncBatchNormalization == plain batch norm over
    the local batch (training mode, then moving stats in inference)."""
    rs = np.random.RandomState(3)
    x = tf.constant(rs.randn(16, 5).astype(np.float32) * 2 + 1)
    bn = hvt_tf.SyncBatchNormalization(momentum=0.0, epsilon=1e-5)
    out = bn(x, training=True)
    mean = x.numpy().mean(0)
    var = x.numpy().var(0)
    expect = (x.numpy() - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(out.numpy(), expect, rtol=1e-4, atol=1e-4)
    # momentum 0 → moving stats == batch stats; inference reproduces
    out2 = bn(x, training=False)
    np.testing.assert_allclose(out2.numpy(), expect, rtol=1e-3, atol=1e-3)


def test_sync_batch_norm_inside_fit():
    """The py_function stats exchange must survive model.fit's compiled
    train step."""
    model = tf.keras.Sequential([
        tf.keras.Input((4,)),
        hvt_tf.SyncBatchNormalization(),
        tf.keras.layers.Dense(1),
    ])
    model.compile(optimizer="sgd", loss="mse")
    X = np.random.RandomState(0).randn(32, 4).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    hist = model.fit(X, y, epochs=2, batch_size=16, verbose=0)
    assert np.isfinite(hist.history["loss"]).all()


def test_sync_batch_norm_gradient_matches_plain_bn():
    """Regression: gradient must flow through the synced statistics —
    single-process sync BN gradients must equal plain batch-norm
    gradients (the py_function exchange is gradient-transparent via the
    local-share surrogate)."""
    rs = np.random.RandomState(11)
    xv = rs.randn(12, 4).astype(np.float32)
    wv = rs.randn(12, 4).astype(np.float32)  # fixed loss projection

    def grads(layer):
        x = tf.constant(xv)
        with tf.GradientTape() as tape:
            tape.watch(x)
            y = layer(x, training=True)
            loss = tf.reduce_sum(y * tf.constant(wv))
        return tape.gradient(loss, x).numpy()

    g_sync = grads(hvt_tf.SyncBatchNormalization(epsilon=1e-5))
    g_ref = grads(tf.keras.layers.BatchNormalization(
        momentum=0.99, epsilon=1e-5))
    np.testing.assert_allclose(g_sync, g_ref, rtol=1e-3, atol=1e-5)


def test_sync_batch_norm_serialization_roundtrip():
    layer = hvt_tf.SyncBatchNormalization(momentum=0.9, epsilon=1e-4,
                                          axis=-1)
    cfg = layer.get_config()
    rebuilt = type(layer).from_config(cfg)
    assert rebuilt.momentum == 0.9 and rebuilt.epsilon == 1e-4
    # full-kwarg reference calls are accepted (GPU knobs ignored)
    hvt_tf.SyncBatchNormalization(beta_initializer="zeros", fused=False)


def test_tensorflow_keras_state_commit_restore_sync(tmp_path):
    import horovod_tpu.tensorflow.elastic as tfe

    model = tf.keras.Sequential([tf.keras.layers.Dense(3)])
    model(tf.zeros([1, 4]))
    opt = tf.keras.optimizers.SGD(0.1)
    opt.build(model.trainable_variables)
    state = tfe.TensorFlowKerasState(model, opt, epoch=0, batch=0)

    committed = [np.array(w, copy=True) for w in model.get_weights()]
    state.commit()
    # mutate, then restore → back to the commit
    model.set_weights([w + 1.0 for w in model.get_weights()])
    state.epoch = 7
    state.restore()
    for a, b in zip(model.get_weights(), committed):
        np.testing.assert_allclose(a, b)
    assert state.epoch == 0
    # sync (1 process): broadcast keeps values, save() refreshes commit
    state.sync()
    for a, b in zip(model.get_weights(), committed):
        np.testing.assert_allclose(a, b)


def test_tensorflow_state_variables_restore():
    import horovod_tpu.tensorflow.elastic as tfe

    v1 = tf.Variable([1.0, 2.0])
    v2 = tf.Variable(5.0)
    state = tfe.TensorFlowState([v1, v2], step=3)
    state.commit()
    v1.assign([9.0, 9.0])
    v2.assign(-1.0)
    state.step = 99
    state.restore()
    np.testing.assert_allclose(v1.numpy(), [1.0, 2.0])
    assert float(v2.numpy()) == 5.0 and state.step == 3


def test_keras_load_model_rewraps_optimizer(tmp_path):
    import horovod_tpu.keras as hvt_keras

    model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
    model.compile(optimizer=tf.keras.optimizers.Adam(1e-3), loss="mse")
    model.fit(np.random.randn(8, 4).astype(np.float32),
              np.random.randn(8, 2).astype(np.float32),
              epochs=1, verbose=0)
    path = str(tmp_path / "m.keras")
    model.save(path)

    loaded = hvt_keras.load_model(path)
    # optimizer came back distributed: a dynamic Keras-native subclass
    # of Adam (compile()-compatible, unlike the bare TF wrapper) whose
    # apply_gradients routes through the collective exchange
    assert getattr(loaded.optimizer, "_hvt_distributed", False)
    assert isinstance(loaded.optimizer, tf.keras.optimizers.Adam)
    pred = loaded.predict(np.zeros((1, 4), np.float32), verbose=0)
    assert pred.shape == (1, 2)
    # retraining through the wrapped optimizer still works under fit
    loaded.fit(np.random.randn(8, 4).astype(np.float32),
               np.random.randn(8, 2).astype(np.float32),
               epochs=1, verbose=0)


def test_graph_mode_backward_passes_per_step_single_process():
    import horovod_tpu.tensorflow as hvt_tf2

    v = tf.Variable([10.0, 20.0])
    opt = hvt_tf2.DistributedOptimizer(
        tf.keras.optimizers.SGD(1.0), backward_passes_per_step=2,
        average_aggregated_gradients=True)

    @tf.function
    def step(g):
        return opt.apply_gradients([(g, v)])

    applied1 = step(tf.constant([1.0, 2.0]))
    assert not bool(applied1)            # accumulation only
    np.testing.assert_allclose(v.numpy(), [10.0, 20.0])
    applied2 = step(tf.constant([3.0, 4.0]))
    assert bool(applied2)                # flush: avg of the two grads
    np.testing.assert_allclose(v.numpy(), [10.0 - 2.0, 20.0 - 3.0])
    # next cycle starts clean
    assert not bool(step(tf.constant([0.0, 0.0])))
    np.testing.assert_allclose(v.numpy(), [8.0, 17.0])


def test_tensorflow_keras_state_unbuilt_optimizer_errors():
    import horovod_tpu.tensorflow.elastic as tfe

    model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
    model(tf.zeros([1, 3]))
    opt = tf.keras.optimizers.SGD(0.1)
    opt.build(model.trainable_variables)
    state = tfe.TensorFlowKerasState(model, opt)
    state.commit()
    # a later restore against an optimizer whose variable count changed
    # must fail loudly, not silently drop slot state
    state._saved_opt = state._saved_opt[:-1]
    with pytest.raises(RuntimeError, match="variables"):
        state.restore()


def test_keras_load_model_custom_optimizer_class(tmp_path):
    import keras

    import horovod_tpu.keras as hvt_keras

    @keras.saving.register_keras_serializable(package="hvt_test")
    class MySGD(tf.keras.optimizers.SGD):
        pass

    model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
    model.compile(optimizer=MySGD(0.01), loss="mse")
    model.fit(np.random.randn(4, 3).astype(np.float32),
              np.random.randn(4, 2).astype(np.float32),
              epochs=1, verbose=0)
    path = str(tmp_path / "c.keras")
    model.save(path)

    loaded = hvt_keras.load_model(path, custom_optimizers=[MySGD])
    assert getattr(loaded.optimizer, "_hvt_distributed", False)
    assert isinstance(loaded.optimizer, MySGD)


def test_keras_commit_state_callback_with_tf_keras_state():
    """CommitStateCallback commits TensorFlowKerasState every N batches
    during a real model.fit (reference _keras/elastic.py wiring)."""
    import horovod_tpu.keras as hvt_keras
    import horovod_tpu.tensorflow.elastic as tfe

    model = tf.keras.Sequential([tf.keras.layers.Dense(1)])
    model.compile(optimizer=tf.keras.optimizers.SGD(0.05), loss="mse")
    model(tf.zeros([1, 3]))
    state = tfe.TensorFlowKerasState(model, model.optimizer, batch=0,
                                     epoch=0)
    commits = []
    orig_commit = state.commit
    state.commit = lambda: (commits.append(1), orig_commit())[1]

    X = np.random.RandomState(0).randn(32, 3).astype(np.float32)
    y = np.zeros((32, 1), np.float32)
    model.fit(X, y, epochs=1, batch_size=8, verbose=0,
              callbacks=[hvt_keras.CommitStateCallback(
                  state, batches_per_commit=2),
                  hvt_keras.UpdateBatchStateCallback(state)])
    assert len(commits) == 2          # 4 batches / commit every 2
    assert state.batch == 0 and state.epoch == 1  # epoch rolled over
    # the last commit snapshot restores cleanly
    state.restore()


def test_graph_mode_aggregation_rejects_changed_variable_list():
    """The in-graph aggregation helper closes over per-variable collective
    names from the call that built it; a later call with a same-length but
    DIFFERENT variable list must raise, not silently reuse stale names."""
    import horovod_tpu.tensorflow as hvt_tf2

    v1 = tf.Variable([1.0, 2.0])
    v2 = tf.Variable([3.0, 4.0])
    opt = hvt_tf2.DistributedOptimizer(
        tf.keras.optimizers.SGD(1.0), backward_passes_per_step=2)

    @tf.function
    def step_a(g):
        return opt.apply_gradients([(g, v1)])

    @tf.function
    def step_b(g):
        return opt.apply_gradients([(g, v2)])

    step_a(tf.constant([1.0, 1.0]))
    with pytest.raises(Exception, match="different variable list"):
        step_b(tf.constant([1.0, 1.0]))


def test_keras_best_model_checkpoint(tmp_path):
    """BestModelCheckpoint parity (reference keras/callbacks.py:151):
    saves only when the monitored metric improves."""
    import horovod_tpu.keras as hvt_keras

    path = str(tmp_path / "best.keras")
    model = tf.keras.Sequential([tf.keras.layers.Dense(1)])
    model.compile(optimizer="sgd", loss="mse")
    cb = hvt_keras.BestModelCheckpoint(monitor="loss", filepath=path)
    X = np.random.RandomState(0).randn(64, 3).astype(np.float32)
    y = (X @ np.asarray([1.0, -1.0, 0.5], np.float32))
    model.fit(X, y, epochs=3, verbose=0, callbacks=[cb])
    assert tf.io.gfile.exists(path)
    with pytest.raises(ValueError, match="filepath"):
        hvt_keras.BestModelCheckpoint(monitor="loss")


def test_keras_distributed_optimizer_preserves_built_slot_state():
    """Wrapping a BUILT optimizer must keep the instance (and its slot
    variables — Adam m/v, iterations) instead of rebuilding via
    from_config, which silently reset momentum on load_model restores."""
    import horovod_tpu.keras as hvt_keras

    opt = tf.keras.optimizers.Adam(0.01)
    model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
    model.compile(optimizer=opt, loss="mse")
    X = np.random.RandomState(0).randn(8, 3).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 2).astype(np.float32)
    model.fit(X, y, epochs=1, verbose=0)  # builds + populates slots
    before = [v.numpy().copy() for v in opt.variables]
    assert int(opt.iterations.numpy()) > 0

    wrapped = hvt_keras.DistributedOptimizer(opt)
    assert wrapped is opt  # the instance survives (class swap, not copy)
    assert getattr(wrapped, "_hvt_distributed", False)
    after = [v.numpy() for v in wrapped.variables]
    assert len(before) == len(after)
    for a, b in zip(before, after):
        np.testing.assert_allclose(a, b)
    # double-wrapping must be a no-op, not a second exchange layer
    assert hvt_keras.DistributedOptimizer(wrapped) is wrapped


def test_keras_wrapped_optimizer_save_load_roundtrip(tmp_path):
    """A model COMPILED with the wrapper round-trips through
    model.save()/load_model: the dynamic subclass serializes under the
    base optimizer's module/name, and slot state survives the reload."""
    import horovod_tpu.keras as hvt_keras

    model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
    model.compile(
        optimizer=hvt_keras.DistributedOptimizer(
            tf.keras.optimizers.Adam(0.01)),
        loss="mse")
    X = np.random.RandomState(0).randn(8, 3).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 2).astype(np.float32)
    model.fit(X, y, epochs=1, verbose=0)
    pre = [v.numpy().copy() for v in model.optimizer.variables]

    path = str(tmp_path / "wrapped.keras")
    model.save(path)  # failed pre-fix: unresolvable dynamic class
    loaded = hvt_keras.load_model(path)
    assert getattr(loaded.optimizer, "_hvt_distributed", False)
    assert isinstance(loaded.optimizer, tf.keras.optimizers.Adam)
    post = [v.numpy() for v in loaded.optimizer.variables]
    assert len(pre) == len(post)
    for a, b in zip(pre, post):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    loaded.fit(X, y, epochs=1, verbose=0)  # retraining still works
