"""Multi-process tests of the native TF custom-op path — the analog of
reference ``test/parallel/test_tensorflow.py`` (allreduce/allgather/
broadcast/alltoall across ranks, grad correctness, error cases) run over
real processes + the C++ engine, exercising eager AND ``tf.function``
graph mode (the reference's custom ops are graph ops;
``tensorflow/mpi_ops.cc:374``)."""

import json
import os
import subprocess
import textwrap

import pytest

from tests.test_engine_integration import REPO, run_workers

TF_OPS_LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build",
                          "libhvt_tf_ops.so")

pytestmark = pytest.mark.skipif(
    not os.path.exists(TF_OPS_LIB),
    reason="TF op library not built (make -C horovod_tpu/csrc tf_ops)")


SHARED = {}     # case -> its two-process body, for the module's one gang


def in_the_shared_gang(case):
    """``case`` returns a two-process body. One gang of two runs all of them,
    one after the other, so that a process imports TensorFlow once and not
    once a case; the test reads the outcome of its own body."""
    SHARED[case.__name__] = textwrap.dedent(case())

    def test(shared_gang):
        outcome = shared_gang(case.__name__)
        assert outcome == "passed", outcome

    return test


@pytest.fixture(scope="module")
def shared_gang(tmp_path_factory):
    """Runs the gang; returns what reads a case's outcome: "passed" when its
    body passed on both ranks, else the rank's traceback, or what the gang
    printed if it ended before the body was reached."""
    folder = tmp_path_factory.mktemp("tf_native_gang")
    try:
        ended = run_tf_workers(f"""
            import json, traceback
            outcomes = {{}}
            for case, body in {SHARED!r}.items():
                try:
                    exec(compile(body, f"<{{case}}>", "exec"), dict(globals()))
                    outcomes[case] = "passed"
                except Exception:
                    outcomes[case] = f"rank {{r}}:\\n" + traceback.format_exc()
                with open({str(folder)!r} + f"/rank{{r}}.json", "w") as f:
                    json.dump(outcomes, f)
        """)
    except (AssertionError, subprocess.TimeoutExpired) as broke:
        ended = f"the gang broke: {broke}"
    ranks = []
    for rank in range(2):
        path = folder / f"rank{rank}.json"
        ranks.append(json.loads(path.read_text()) if path.exists() else {})

    def outcome(case):
        said = [of.get(case, f"rank {rank} never reached it\n{ended}")
                for rank, of in enumerate(ranks)]
        return next((o for o in said if o != "passed"), "passed")

    return outcome


def run_tf_workers(body, np=2, timeout=240, **kw):
    env = dict(kw.pop("extra_env", None) or {})
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    return run_workers(
        "import tensorflow as tf\nimport horovod_tpu.tensorflow as hvd\n"
        "assert hvd._native() is not None, 'native op path not active'\n"
        + textwrap.dedent(body), np=np, timeout=timeout, extra_env=env,
        **kw)


@in_the_shared_gang
def test_native_allreduce_eager_average():
    return """
        x = tf.fill([4], float(r + 1))
        res = hvd.allreduce(x, name="t")
        assert isinstance(res, tf.Tensor)
        np.testing.assert_allclose(res.numpy(), (1 + n) / 2.0)
    """


@in_the_shared_gang
def test_native_allreduce_inside_tf_function():
    # collectives traced INTO the graph — impossible on the numpy bridge
    return """
        @tf.function
        def step(x):
            return hvd.allreduce(x, name="graph.t", average=False) * 2.0

        out = step(tf.fill([3], float(r + 1)))
        np.testing.assert_allclose(out.numpy(), 2.0 * sum(
            i + 1 for i in range(n)))
        # second call reuses the traced graph (same tensor name, engine
        # cache hit path)
        out2 = step(tf.fill([3], float(r + 1)))
        np.testing.assert_allclose(out2.numpy(), out.numpy())
    """


@in_the_shared_gang
def test_native_allreduce_dtypes():
    return """
        for dt in (tf.float32, tf.float64, tf.int32, tf.int64,
                   tf.float16, tf.bfloat16):
            x = tf.cast(tf.range(6) + r, dt)
            res = hvd.allreduce(x, name=f"d{dt.name}", average=False)
            expected = sum((np.arange(6) + i) for i in range(n))
            np.testing.assert_allclose(
                tf.cast(res, tf.float64).numpy(), expected)
    """


@in_the_shared_gang
def test_native_allgather_uneven_rows():
    return """
        rows = r + 1
        res = hvd.allgather(tf.fill([rows, 3], float(r)), name="ag")
        assert res.shape == (n * (n + 1) // 2, 3), res.shape
        np.testing.assert_allclose(res.numpy()[0], 0.0)
        np.testing.assert_allclose(res.numpy()[1:], 1.0)
    """


@in_the_shared_gang
def test_native_broadcast_and_alltoall():
    return """
        b = hvd.broadcast(tf.fill([4], float(r + 7)), root_rank=1,
                          name="bc")
        np.testing.assert_allclose(b.numpy(), 8.0)

        # default (even) splits: row d of each rank's payload goes to
        # rank d, so rank r receives [r_row from rank 0, r_row from 1, ..]
        ev, evr = hvd.alltoall(
            tf.constant([[10.0 * r + d] for d in range(n)]),
            name="a2a.even")
        np.testing.assert_allclose(
            ev.numpy().ravel(), [10.0 * s + r for s in range(n)])
        assert list(evr.numpy()) == [1] * n

        payload = tf.constant([[float(r)], [float(r) + 10.0],
                               [float(r) + 10.0]])
        out, recv = hvd.alltoall(payload, splits=[1, 2], name="a2a")
        if r == 0:
            np.testing.assert_allclose(out.numpy().ravel(), [0.0, 1.0])
            np.testing.assert_allclose(recv.numpy(), [1, 1])
        else:
            np.testing.assert_allclose(out.numpy().ravel(),
                                       [10.0, 10.0, 11.0, 11.0])
            np.testing.assert_allclose(recv.numpy(), [2, 2])
    """


@in_the_shared_gang
def test_native_tape_gradient_is_allreduced():
    # gradient of allreduce = allreduce of gradient (registered grad fn,
    # reference tensorflow/mpi_ops.py:116)
    return """
        v = tf.Variable(tf.fill([3], float(r + 1)))
        with tf.GradientTape() as tape:
            y = hvd.allreduce(v, name="g", average=False)
            loss = tf.reduce_sum(y) * (r + 1.0)
        g = tape.gradient(loss, v)
        # upstream grad on rank i is (i+1); summed across ranks
        np.testing.assert_allclose(g.numpy(), float(sum(
            i + 1 for i in range(n))))
    """


@in_the_shared_gang
def test_native_distributed_gradient_tape_in_tf_function():
    return """
        v = tf.Variable([float(r + 1), 2.0 * (r + 1)])

        @tf.function
        def step():
            with tf.GradientTape() as tape:
                loss = tf.reduce_sum(v * v)
            dtape = hvd.DistributedGradientTape(tape)
            return dtape.gradient(loss, v)

        g = step()
        expected = np.mean([[2.0 * (i + 1), 4.0 * (i + 1)]
                            for i in range(n)], axis=0)
        np.testing.assert_allclose(g.numpy(), expected)
    """


@in_the_shared_gang
def test_native_size_rank_ops_dynamic():
    return """
        assert int(hvd.size_op()) == n
        assert int(hvd.rank_op()) == r
    """


def test_native_shape_mismatch_errors_not_hangs():
    # cross-rank shape mismatch → per-tensor ERROR response surfaced as a
    # TF error on every rank (reference controller.cc:481-706 semantics)
    run_tf_workers("""
        x = tf.fill([3 + r], 1.0)
        try:
            hvd.allreduce(x, name="bad")
        except Exception as e:
            assert "bad" in str(e) or "mismatch" in str(e).lower(), str(e)
        else:
            raise AssertionError("mismatched allreduce did not error")
    """)


@in_the_shared_gang
def test_native_graph_backward_passes_per_step():
    # in-graph aggregation (tf.Variables + tf.cond) composed with the
    # native allreduce: 2 accumulation passes, then one averaged update
    return """
        v = tf.Variable([0.0, 0.0])
        opt = hvd.DistributedOptimizer(
            tf.keras.optimizers.SGD(1.0), backward_passes_per_step=2)

        @tf.function
        def step(g):
            return opt.apply_gradients([(g, v)])

        a1 = step(tf.constant([float(r + 1), 1.0]))
        assert not bool(a1)
        np.testing.assert_allclose(v.numpy(), 0.0)   # accumulating
        a2 = step(tf.constant([float(r + 1), 1.0]))
        assert bool(a2)
        # per-rank sum over 2 passes = 2*(r+1); averaged across ranks
        exp0 = -2.0 * np.mean([i + 1 for i in range(n)])
        np.testing.assert_allclose(v.numpy(), [exp0, -2.0], rtol=1e-6)
    """


def test_native_process_set_allreduce_4proc():
    # subset collective over the native op path: members reduce among
    # themselves; non-members run a disjoint set concurrently
    run_tf_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        even = ProcessSet([0, 2])
        odd = ProcessSet([1, 3])
        mine = even if r % 2 == 0 else odd
        x = tf.fill([3], float(r + 1))
        res = hvd.allreduce(x, name="ps.even" if r % 2 == 0 else "ps.odd",
                            average=False, process_set=mine)
        expected = sum(i + 1 for i in mine.ranks)
        np.testing.assert_allclose(res.numpy(), float(expected))

        # unnamed eager subset collectives raise with guidance
        try:
            hvd.allreduce(x, process_set=mine)
        except ValueError as e:
            assert "name" in str(e), e
        else:
            raise AssertionError("unnamed process-set allreduce passed")
    """, np=4)


@in_the_shared_gang
def test_native_reducescatter_2proc():
    return """
        # 4 rows, 2 ranks: each keeps 2 reduced rows
        x = tf.reshape(tf.range(8, dtype=tf.float32), [4, 2]) + float(r)
        out = hvd.reducescatter(x, name="rs")
        full = sum(np.arange(8, dtype=np.float32).reshape(4, 2) + i
                   for i in range(n))
        np.testing.assert_allclose(out.numpy(), full[r * 2:(r + 1) * 2])

        # in-graph with gradient: grad of reduce-scatter = allgather
        v = tf.Variable(tf.ones([4, 2]) * (r + 1.0))

        @tf.function
        def step():
            with tf.GradientTape() as tape:
                y = hvd.reducescatter(v, name="rs.g")
                loss = tf.reduce_sum(y) * (r + 1.0)
            return tape.gradient(loss, v)

        g = step()
        # each rank's shard contributes its owner's upstream factor
        expect = np.concatenate([np.full((2, 2), float(i + 1))
                                 for i in range(n)])
        np.testing.assert_allclose(g.numpy(), expect)

        # AVERAGE: forward divides by n, so must the gradient
        from horovod_tpu.ops import collective_ops as C
        w = tf.Variable(tf.ones([4, 2]))
        with tf.GradientTape() as tape:
            y = hvd.reducescatter(w, name="rs.avg", op=C.Average)
            loss = tf.reduce_sum(y)
        ga = tape.gradient(loss, w)
        np.testing.assert_allclose(ga.numpy(), 1.0 / n)
    """


@in_the_shared_gang
def test_tf_join_uneven_steps_2proc():
    # reference HorovodJoinOp semantics: rank 1 joins early; rank 0's
    # later collectives proceed with zero stand-ins
    return """
        steps = 3 if r == 0 else 1
        for i in range(steps):
            res = hvd.allreduce(tf.ones([2]), name=f"j{i}", average=False)
            if i < 1:
                np.testing.assert_allclose(res.numpy(), float(n))
            else:
                np.testing.assert_allclose(res.numpy(), 1.0)
        last = hvd.join()
        assert last == 0, last  # rank 0 ran more steps → joined last
    """


@in_the_shared_gang
def test_native_alltoall_gradient_2proc():
    # grad of alltoall routes each received block's gradient back to its
    # sender via the forward's negotiated received_splits (reference
    # tensorflow/mpi_ops.py alltoall gradient)
    return """
        splits = [1, 2] if r == 0 else [2, 1]
        v = tf.Variable(
            tf.reshape(tf.range(3, dtype=tf.float32) + 10.0 * r, [3, 1]))

        @tf.function
        def step():
            with tf.GradientTape() as tape:
                out, recv = hvd.alltoall(v, splits=splits, name="a2a.g")
                loss = tf.reduce_sum(out) * (r + 1.0)
            return tape.gradient(loss, v)

        g = step()
        # rank 0 kept row 0 (factor 1), sent rows 1-2 to rank 1 (factor 2)
        # rank 1 sent rows 0-1 to rank 0 (factor 1), kept row 2 (factor 2)
        expect = [[1.0], [2.0], [2.0]] if r == 0 else [[1.0], [1.0], [2.0]]
        np.testing.assert_allclose(g.numpy(), expect)
    """


@in_the_shared_gang
def test_native_zero_width_rows_keep_true_row_count():
    # trailing dim 0 → row_bytes 0; dim 0 must come from the negotiated
    # splits, not result_bytes/row_bytes
    return """
        rows = r + 1
        res = hvd.allgather(tf.zeros([rows, 0]), name="agz")
        assert tuple(res.shape) == (n * (n + 1) // 2, 0), res.shape

        out, recv = hvd.alltoall(tf.zeros([n, 0]), name="a2az")
        assert tuple(out.shape) == (n, 0), out.shape
        assert list(recv.numpy()) == [1] * n
    """


@in_the_shared_gang
def test_native_local_ops_and_grouped_allreduce():
    return """
        assert int(hvd.local_size_op()) == n     # single host: local == world
        assert int(hvd.local_rank_op()) == r
        outs = hvd.grouped_allreduce(
            [tf.fill([2], float(r + 1)), tf.fill([3], float(2 * (r + 1)))],
            name="ga", average=False)
        s = sum(i + 1 for i in range(n))
        np.testing.assert_allclose(outs[0].numpy(), float(s))
        np.testing.assert_allclose(outs[1].numpy(), float(2 * s))
    """


@in_the_shared_gang
def test_native_two_unnamed_grouped_allreduces_in_one_tf_function():
    # two name=None groups traced into ONE step must land on distinct
    # per-node names (a baked default would collide and mis-pair)
    return """
        @tf.function
        def step(a, b):
            g1 = hvd.grouped_allreduce([a], average=False)
            g2 = hvd.grouped_allreduce([b], average=False)
            return g1[0], g2[0]

        o1, o2 = step(tf.fill([2], float(r + 1)),
                      tf.fill([2], float(100 * (r + 1))))
        s = sum(i + 1 for i in range(n))
        np.testing.assert_allclose(o1.numpy(), float(s))
        np.testing.assert_allclose(o2.numpy(), float(100 * s))
    """
