"""ComputationGraph tests.

Mirrors the reference suites ``nn/graph/TestComputationGraphNetwork.java``
(behavioral) and ``gradientcheck/GradientCheckTestsComputationGraph.java``
(numerical backbone).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_builder import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.graph_vertices import (
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    L2NormalizeVertex,
    L2Vertex,
    LastTimeStepVertex,
    MergeVertex,
    ReshapeVertex,
    ReverseTimeSeriesVertex,
    ScaleVertex,
    ShiftVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer,
    LSTM,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.gradient_check import check_gradients_graph
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _simple_graph(seed=12345):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater("sgd")
        .graph_builder()
        .add_inputs("in")
        .add_layer("d0", DenseLayer(n_out=8, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax", loss="mcxent"), "d0")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )
    return ComputationGraph(conf).init()


def _iris_like(n=60, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


class TestBasics:
    def test_fit_reduces_score(self):
        net = _simple_graph()
        ds = _iris_like()
        s0 = net.score(ds)
        net.fit(ListDataSetIterator(ds, 16), epochs=20)
        assert net.score(ds) < s0

    def test_output_shape(self):
        net = _simple_graph()
        y = net.output_single(np.zeros((5, 4), np.float32))
        assert y.shape == (5, 3)
        np.testing.assert_allclose(y.sum(axis=1), 1.0, rtol=1e-5)

    def test_serde_roundtrip(self):
        net = _simple_graph()
        js = net.conf.to_json()
        conf2 = ComputationGraphConfiguration.from_json(js)
        assert conf2 == net.conf
        net2 = ComputationGraph(conf2).init()
        assert net2.num_params() == net.num_params()

    def test_clone_and_params_flat(self):
        net = _simple_graph()
        ds = _iris_like()
        net.fit(ds, batch_size=16)
        c = net.clone()
        np.testing.assert_array_equal(c.params_flat(), net.params_flat())
        x = np.random.default_rng(1).standard_normal((3, 4)).astype(np.float32)
        np.testing.assert_allclose(c.output_single(x), net.output_single(x), rtol=1e-6)

    def test_params_flat_roundtrip(self):
        net = _simple_graph()
        vec = net.params_flat()
        net2 = _simple_graph(seed=999)
        net2.set_params_flat(vec)
        np.testing.assert_array_equal(net2.params_flat(), vec)

    def test_mln_parity(self):
        """Same layers as a graph and as an MLN with identical params give
        identical outputs (reference testConfigurationBasic-style parity)."""
        mln_conf = (
            NeuralNetConfiguration.builder().seed(12345).updater("sgd").list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build()
        )
        mln = MultiLayerNetwork(mln_conf).init()
        cg = _simple_graph()
        cg.set_params_flat(mln.params_flat())
        x = np.random.default_rng(2).standard_normal((7, 4)).astype(np.float32)
        np.testing.assert_allclose(cg.output_single(x), mln.output(x), rtol=1e-5)


class TestMultiInputOutput:
    def _two_in_two_out(self):
        return (
            NeuralNetConfiguration.builder().seed(1).updater("sgd")
            .graph_builder()
            .add_inputs("inA", "inB")
            .add_layer("dA", DenseLayer(n_out=6, activation="relu"), "inA")
            .add_layer("dB", DenseLayer(n_out=6, activation="relu"), "inB")
            .add_vertex("merge", MergeVertex(), "dA", "dB")
            .add_layer("outA", OutputLayer(n_out=2, activation="softmax", loss="mcxent"), "merge")
            .add_layer("outB", OutputLayer(n_out=1, activation="identity", loss="mse"), "merge")
            .set_outputs("outA", "outB")
            .set_input_types(InputType.feed_forward(3), InputType.feed_forward(5))
            .build()
        )

    def test_merge_shapes(self):
        net = ComputationGraph(self._two_in_two_out()).init()
        rng = np.random.default_rng(0)
        a = rng.standard_normal((4, 3)).astype(np.float32)
        b = rng.standard_normal((4, 5)).astype(np.float32)
        ya, yb = net.output(a, b)
        assert ya.shape == (4, 2)
        assert yb.shape == (4, 1)

    def test_fit_multidataset(self):
        net = ComputationGraph(self._two_in_two_out()).init()
        rng = np.random.default_rng(0)
        n = 32
        mds = MultiDataSet(
            [rng.standard_normal((n, 3)).astype(np.float32),
             rng.standard_normal((n, 5)).astype(np.float32)],
            [np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)],
             rng.standard_normal((n, 1)).astype(np.float32)],
        )
        s0 = net.score(mds)
        for _ in range(30):
            net.fit(mds)
        assert net.score(mds) < s0

    def test_gradients_multi(self):
        net = ComputationGraph(self._two_in_two_out()).init()
        rng = np.random.default_rng(3)
        n = 4
        mds = MultiDataSet(
            [rng.standard_normal((n, 3)), rng.standard_normal((n, 5))],
            [np.eye(2)[rng.integers(0, 2, n)], rng.standard_normal((n, 1))],
        )
        assert check_gradients_graph(net, mds, print_results=True)


class TestVertices:
    def test_elementwise_ops(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal((3, 4)).astype(np.float32)
        b = rng.standard_normal((3, 4)).astype(np.float32)
        import jax.numpy as jnp

        cases = {
            "add": a + b, "subtract": a - b, "product": a * b,
            "average": (a + b) / 2, "max": np.maximum(a, b),
        }
        for op, want in cases.items():
            got = ElementWiseVertex(op).apply([jnp.asarray(a), jnp.asarray(b)], [None, None])
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, err_msg=op)

    def test_residual_add_graph(self):
        """Skip connection: the shape every ResNet block needs."""
        conf = (
            NeuralNetConfiguration.builder().seed(5).updater("sgd")
            .graph_builder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=4, activation="relu"), "in")
            .add_vertex("res", ElementWiseVertex("add"), "d1", "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax", loss="mcxent"), "res")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4))
            .build()
        )
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        ds = DataSet(rng.standard_normal((4, 4)), np.eye(2)[rng.integers(0, 2, 4)])
        assert check_gradients_graph(net, ds, print_results=True)

    def test_subset_scale_shift(self):
        import jax.numpy as jnp

        x = jnp.arange(12.0).reshape(2, 6)
        got = SubsetVertex(1, 3).apply([x], [None])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[:, 1:4])
        np.testing.assert_allclose(np.asarray(ScaleVertex(2.0).apply([x], [None])), np.asarray(x) * 2)
        np.testing.assert_allclose(np.asarray(ShiftVertex(1.5).apply([x], [None])), np.asarray(x) + 1.5)

    def test_stack_unstack(self):
        import jax.numpy as jnp

        a = jnp.ones((2, 3))
        b = jnp.zeros((2, 3))
        s = StackVertex().apply([a, b], [None, None])
        assert s.shape == (4, 3)
        u0 = UnstackVertex(0, 2).apply([s], [None])
        u1 = UnstackVertex(1, 2).apply([s], [None])
        np.testing.assert_array_equal(np.asarray(u0), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(u1), np.asarray(b))

    def test_l2_vertices(self):
        import jax.numpy as jnp

        a = jnp.asarray([[3.0, 4.0]])
        b = jnp.zeros((1, 2))
        d = L2Vertex(eps=0.0).apply([a, b], [None, None])
        np.testing.assert_allclose(np.asarray(d), [[5.0]], rtol=1e-6)
        n = L2NormalizeVertex(eps=0.0).apply([a], [None])
        np.testing.assert_allclose(np.asarray(n), [[0.6, 0.8]], rtol=1e-6)

    def test_reshape_vertex(self):
        import jax.numpy as jnp

        x = jnp.arange(24.0).reshape(2, 12)
        y = ReshapeVertex([-1, 3, 4]).apply([x], [None])
        assert y.shape == (2, 3, 4)

    def test_pool_helper_vertex(self):
        """reference PoolHelperVertex.doForward: strip the first spatial
        row+column (NHWC here; NCHW [:, :, 1:, 1:] there)."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf.graph_vertices import (
            PoolHelperVertex,
        )
        from deeplearning4j_tpu.nn.conf.input_type import InputType

        v = PoolHelperVertex()
        ot = v.get_output_type(InputType.convolutional(8, 8, 3))
        assert (ot.height, ot.width, ot.channels) == (7, 7, 3)
        x = jnp.arange(2.0 * 8 * 8 * 3).reshape(2, 8, 8, 3)
        y = v.apply([x], [None])
        assert y.shape == (2, 7, 7, 3)
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(x)[:, 1:, 1:, :])

    def test_reverse_timeseries_masked(self):
        import jax.numpy as jnp

        x = jnp.asarray(np.arange(8.0).reshape(1, 4, 2))
        m = jnp.asarray([[1.0, 1.0, 1.0, 0.0]])
        y = np.asarray(ReverseTimeSeriesVertex().apply([x], [m]))
        # valid prefix [t0,t1,t2] reversed; padded step t3 untouched
        np.testing.assert_array_equal(y[0, 0], [4.0, 5.0])
        np.testing.assert_array_equal(y[0, 2], [0.0, 1.0])
        np.testing.assert_array_equal(y[0, 3], [6.0, 7.0])

    def test_last_time_step_masked(self):
        import jax.numpy as jnp

        x = jnp.asarray(np.arange(12.0).reshape(1, 6, 2))
        m = jnp.asarray([[1.0, 1.0, 1.0, 1.0, 0.0, 0.0]])
        y = np.asarray(LastTimeStepVertex().apply([x], [m]))
        np.testing.assert_array_equal(y, [[6.0, 7.0]])


class TestRnnGraph:
    def test_seq2class_graph(self):
        """LSTM encoder → LastTimeStep vertex → classifier; masked."""
        conf = (
            NeuralNetConfiguration.builder().seed(7).updater("adam")
            .graph_builder()
            .add_inputs("in")
            .add_layer("lstm", LSTM(n_out=8, activation="tanh"), "in")
            .add_vertex("last", LastTimeStepVertex("in"), "lstm")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax", loss="mcxent"), "last")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(3))
            .build()
        )
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        n, T = 16, 7
        x = rng.standard_normal((n, T, 3)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
        mask = (np.arange(T)[None, :] < rng.integers(3, T + 1, n)[:, None]).astype(np.float32)
        ds = DataSet(x, y, features_mask=mask)
        s0 = net.score(ds)
        net.fit(ListDataSetIterator(ds, 8), epochs=10)
        assert net.score(ds) < s0
        out = net.output_single(x, masks=[mask])
        assert out.shape == (n, 2)

    def test_duplicate_to_timeseries(self):
        """Encoder-decoder shape: static vector broadcast over time."""
        conf = (
            NeuralNetConfiguration.builder().seed(7).updater("sgd")
            .graph_builder()
            .add_inputs("seq", "static")
            .add_layer("dstatic", DenseLayer(n_out=4, activation="tanh"), "static")
            .add_vertex("dup", DuplicateToTimeSeriesVertex("seq"), "dstatic", "seq")
            .add_vertex("merge", MergeVertex(), "seq", "dup")
            .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"), "merge")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(3), InputType.feed_forward(5))
            .build()
        )
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        n, T = 4, 5
        mds = MultiDataSet(
            [rng.standard_normal((n, T, 3)).astype(np.float32),
             rng.standard_normal((n, 5)).astype(np.float32)],
            [np.eye(2, dtype=np.float32)[rng.integers(0, 2, (n, T))]],
        )
        ys = net.output(mds.features[0], mds.features[1])
        assert ys[0].shape == (n, T, 2)
        assert check_gradients_graph(net, mds, print_results=True)


class TestGraphGradients:
    def test_simple_graph_gradients(self):
        net = _simple_graph()
        ds = _iris_like(n=5, seed=3)
        assert check_gradients_graph(net, ds, print_results=True)

    def test_cycle_detection(self):
        with pytest.raises(ValueError, match="cycle"):
            (
                NeuralNetConfiguration.builder().graph_builder()
                .add_inputs("in")
                .add_layer("a", DenseLayer(n_out=4), "in", "b")
                .add_layer("b", DenseLayer(n_out=4), "a")
                .add_layer("out", OutputLayer(n_out=2), "b")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4))
                .build()
            )

    def test_unknown_input_rejected(self):
        with pytest.raises(ValueError, match="does not exist"):
            (
                NeuralNetConfiguration.builder().graph_builder()
                .add_inputs("in")
                .add_layer("a", DenseLayer(n_out=4), "nope")
                .set_outputs("a")
                .build()
            )


class TestGraphSerialization:
    def test_checkpoint_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.train.model_serializer import (
            ModelGuesser,
            ModelSerializer,
        )

        net = _simple_graph()
        ds = _iris_like()
        net.fit(ds, batch_size=16)
        p = str(tmp_path / "graph.zip")
        ModelSerializer.write_model(net, p)
        net2 = ModelSerializer.restore_computation_graph(p)
        np.testing.assert_array_equal(net2.params_flat(), net.params_flat())
        np.testing.assert_array_equal(net2.opt_state_flat(), net.opt_state_flat())
        assert net2.iteration == net.iteration
        x = np.random.default_rng(0).standard_normal((3, 4)).astype(np.float32)
        np.testing.assert_allclose(net2.output_single(x), net.output_single(x), rtol=1e-6)
        # guesser dispatches on meta model_type
        net3 = ModelGuesser.load_model_guess(p)
        np.testing.assert_array_equal(net3.params_flat(), net.params_flat())


class TestGraphParallel:
    def test_graph_under_parallel_wrapper(self):
        """ComputationGraph + data-parallel wrapper on the 8-device mesh."""
        from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        net = _simple_graph()
        ds = _iris_like(n=24)
        pw = ParallelWrapper(net)
        s_before = net.score(ds)
        pw.fit(ExistingDataSetIterator(ds.batch_by(24)), epochs=15)
        assert net.iteration == 15
        assert net.score(ds) < s_before

    def test_duplicate_vertex_reference_style(self):
        """Constructor-arg-only usage (reference API): timestep source is
        auto-wired as a graph edge."""
        conf = (
            NeuralNetConfiguration.builder().seed(7).updater("sgd")
            .graph_builder()
            .add_inputs("seq", "static")
            .add_layer("dstatic", DenseLayer(n_out=4, activation="tanh"), "static")
            .add_vertex("dup", DuplicateToTimeSeriesVertex("seq"), "dstatic")
            .add_vertex("merge", MergeVertex(), "seq", "dup")
            .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"), "merge")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(3), InputType.feed_forward(5))
            .build()
        )
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        n, T = 3, 4
        ys = net.output(
            rng.standard_normal((n, T, 3)).astype(np.float32),
            rng.standard_normal((n, 5)).astype(np.float32),
        )
        assert ys[0].shape == (n, T, 2)

    def test_non_output_layer_output_rejected(self):
        conf = (
            NeuralNetConfiguration.builder().graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=4), "in")
            .set_outputs("d")
            .set_input_types(InputType.feed_forward(4))
            .build()
        )
        with pytest.raises(ValueError, match="not an output layer"):
            ComputationGraph(conf)


class TestGraphSerdeOrdering:
    def test_topo_order_survives_json_roundtrip(self):
        """Non-alphabetical parallel branches: flattened-param order must be
        identical after a JSON round-trip (regression: sort_keys used to
        reorder vertex insertion order and corrupt restored params)."""
        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.graph_vertices import MergeVertex
        from deeplearning4j_tpu.nn.conf.input_type import InputType
        from deeplearning4j_tpu.nn.conf.layers.core import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.conf.graph_builder import (
            ComputationGraphConfiguration,
        )

        gb = (
            NeuralNetConfiguration.builder().seed(1).graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("z1", DenseLayer(n_out=5, activation="relu"), "in")
            .add_layer("a2", DenseLayer(n_out=5, activation="relu"), "in")
            .add_vertex("merge", MergeVertex(), "z1", "a2")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax", loss="mcxent"), "merge")
            .set_outputs("out")
        )
        conf = gb.build()
        conf2 = ComputationGraphConfiguration.from_json(conf.to_json())
        assert conf2.topological_order == conf.topological_order
        net = ComputationGraph(conf).init()
        net2 = ComputationGraph(conf2).init()
        assert net2.layer_names == net.layer_names

    def test_multi_input_layer_auto_merges(self):
        """A layer declared with two inputs gets an implicit MergeVertex
        (reference GraphBuilder behavior) instead of silently dropping
        the second input."""
        import numpy as np

        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.input_type import InputType
        from deeplearning4j_tpu.nn.conf.layers.core import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        gb = (
            NeuralNetConfiguration.builder().seed(1).graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("dA", DenseLayer(n_out=3, activation="relu"), "in")
            .add_layer("dB", DenseLayer(n_out=5, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax", loss="mcxent"),
                       "dA", "dB")
            .set_outputs("out")
        )
        net = ComputationGraph(gb.build()).init()
        # out's weight matrix must see merged width 3+5=8
        assert net.params_["out"]["W"].shape == (8, 2)
        y = net.output_single(np.zeros((2, 4), np.float32))
        assert y.shape == (2, 2)

    def test_unstack_indivisible_batch_raises(self):
        import jax.numpy as jnp
        import pytest

        from deeplearning4j_tpu.nn.conf.graph_vertices import UnstackVertex

        v = UnstackVertex(from_idx=0, stack_size=2)
        with pytest.raises(ValueError, match="not divisible"):
            v.apply([jnp.zeros((5, 3))], [None])


class TestGraphSummary:
    def test_summary_table(self):
        from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.conf.graph_vertices import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (NeuralNetConfiguration.builder().seed(1).graph_builder()
                .add_inputs("a", "b")
                .add_layer("d1", DenseLayer(n_out=4, activation="relu"), "a")
                .add_layer("d2", DenseLayer(n_out=4, activation="relu"), "b")
                .add_vertex("m", MergeVertex(), "d1", "d2")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                              loss="mcxent"), "m")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(3),
                                 InputType.feed_forward(3)).build())
        cg = ComputationGraph(conf).init()
        s = cg.summary()
        assert "NetworkInput" in s and "MergeVertex" in s
        assert f"Total parameters: {cg.num_params():,}" in s


def test_fit_phases_cover_the_step():
    """ComputationGraph.fit on a DataSet: train.iterate / put_batch /
    dispatch (/ fetch_loss with a listener), at most four a step, nested or
    disjoint, covering the wall time of fit; phases on, nothing retraces."""
    import time

    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.train.listeners import ScoreIterationListener
    from tests.phase_checks import assert_nested_or_disjoint, covered_ns

    # wide enough that a step outweighs the interpreter's own time between
    # two phases (some tens of microseconds), as on the chip
    conf = (
        NeuralNetConfiguration.builder().seed(5).updater("sgd")
        .graph_builder().add_inputs("in")
        .add_layer("d0", DenseLayer(n_out=512, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"), "d0")
        .set_outputs("out").set_input_types(InputType.feed_forward(256))
        .build()
    )
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(3)
    ds = DataSet(rng.standard_normal((8192, 256)).astype(np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8192)])
    net.fit(ds, epochs=1, batch_size=2048)  # compiles
    with obs_trace.RetraceMonitor() as mon:
        mark = time.time_ns()
        net.fit(ds, epochs=1, batch_size=2048)
        done = time.time_ns()
        got = [e for e in obs_trace.phases(mark) if e[0].startswith("train.")]
        # no listener: the program fetches nothing, so no fetch_loss
        assert [e[0] for e in got] == ["train.iterate", "train.put_batch",
                                       "train.dispatch"] * 4
        net.listeners.append(ScoreIterationListener(1))
        mark = time.time_ns()
        net.fit(ds, epochs=2, batch_size=2048)
        done = time.time_ns()
    assert mon.total() == 0, mon.delta()
    got = [e for e in obs_trace.phases(mark) if e[0].startswith("train.")]
    assert [e[0] for e in got] == ["train.iterate", "train.put_batch",
                                   "train.dispatch", "train.fetch_loss"] * 8
    assert_nested_or_disjoint(got)
    # from the first batch's iterate to the last step's end: fit's own
    # prologue and epilogue (epoch hooks, iterator reset, the prefetch
    # thread's start and join) are no part of a step
    lo, hi = got[0][1], max(a + d for _, a, d in got)
    assert mark <= lo and hi <= done
    assert covered_ns(got, lo, hi) >= 0.95 * (hi - lo)
