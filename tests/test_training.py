"""Training-stack tests: updaters, fit loop, serialization, evaluation.

Mirrors the reference's core test style (MultiLayerTest, BackPropMLPTest,
updater tests — SURVEY.md §4): tiny nets, fixed seeds, convergence and
round-trip assertions.
"""

import os
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.eval import (
    Evaluation,
    EvaluationBinary,
    EvaluationCalibration,
    RegressionEvaluation,
    ROC,
    ROCMultiClass,
)
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import (
    BatchNorm,
    Conv2D,
    Dense,
    DropoutLayer,
    LSTM,
    OutputLayer,
    RnnOutputLayer,
    SimpleRnn,
    Subsampling2D,
)
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.train import (
    CollectScoresListener,
    ScoreIterationListener,
    make_updater,
    schedule_value,
)
from deeplearning4j_tpu.train.updaters import apply_gradient_normalization
from deeplearning4j_tpu.utils.serialization import restore_network, save_network


def two_moons(n=200, seed=0):
    """Tiny separable 2-class dataset."""
    rs = np.random.RandomState(seed)
    n2 = n // 2
    t = rs.uniform(0, np.pi, n2)
    x0 = np.stack([np.cos(t), np.sin(t)], -1) + 0.1 * rs.randn(n2, 2)
    x1 = np.stack([1 - np.cos(t), 0.5 - np.sin(t)], -1) + 0.1 * rs.randn(n2, 2)
    x = np.concatenate([x0, x1]).astype(np.float32)
    y = np.zeros((n, 2), np.float32)
    y[:n2, 0] = 1
    y[n2:, 1] = 1
    perm = rs.permutation(n)
    return x[perm], y[perm]


class TestUpdaters:
    @pytest.mark.parametrize(
        "spec",
        ["sgd", "adam", "adamax", "nadam", "amsgrad", "nesterovs", "adagrad",
         "rmsprop", {"type": "adadelta"}],
    )
    def test_minimizes_quadratic(self, spec):
        u = make_updater(spec if isinstance(spec, dict) else {"type": spec, "lr": 0.1})
        params = {"w": jnp.array([3.0, -2.0])}
        s = u.init(params)
        for it in range(1000):
            g = {"w": 2 * params["w"]}  # d/dw of w^2
            upd, s = u.update(g, s, params, it)
            params = jax.tree_util.tree_map(lambda p, d: p - d, params, upd)
        assert float(jnp.abs(params["w"]).max()) < 0.3, spec

    def test_noop_does_nothing(self):
        u = make_updater("noop")
        params = {"w": jnp.array([1.0])}
        upd, _ = u.update({"w": jnp.array([5.0])}, u.init(params), params, 0)
        assert float(upd["w"][0]) == 0.0

    def test_schedules(self):
        assert float(schedule_value(None, 0.1, 5)) == pytest.approx(0.1)
        assert float(schedule_value({"policy": "exponential", "decay_rate": 0.5}, 1.0, 2)) == pytest.approx(0.25)
        assert float(schedule_value({"policy": "step", "decay_rate": 0.1, "step_size": 10}, 1.0, 25)) == pytest.approx(0.01)
        m = schedule_value({"policy": "map", "schedule": {"0": 1.0, "10": 0.5}}, 1.0, 15)
        assert float(m) == pytest.approx(0.5)
        w = schedule_value({"policy": "warmup_cosine", "warmup": 10, "max_iter": 110}, 1.0, 5)
        assert float(w) == pytest.approx(0.5)

    def test_gradient_normalization_modes(self):
        g = {"W": jnp.array([3.0, 4.0]), "b": jnp.array([0.0])}
        out = apply_gradient_normalization("clip_l2_per_layer", 1.0, g)
        norm = float(jnp.sqrt(sum(jnp.sum(v * v) for v in jax.tree_util.tree_leaves(out))))
        assert norm == pytest.approx(1.0, rel=1e-4)
        out = apply_gradient_normalization("clip_elementwise_absolute_value", 2.0, g)
        assert float(out["W"].max()) == pytest.approx(2.0)
        out = apply_gradient_normalization("renormalize_l2_per_param_type", 1.0, g)
        assert float(jnp.linalg.norm(out["W"])) == pytest.approx(1.0, rel=1e-3)


class TestMultiLayerNetwork:
    def _mlp_conf(self, updater="adam", **kw):
        return MultiLayerConfiguration(
            layers=(
                Dense(n_out=16, activation="tanh"),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent"),
            ),
            input_type=InputType.feed_forward(2),
            updater={"type": updater, "lr": 0.05},
            seed=42,
            **kw,
        )

    def test_fit_reduces_score_and_classifies(self):
        x, y = two_moons()
        model = MultiLayerNetwork(self._mlp_conf()).init()
        scores = CollectScoresListener()
        model.set_listeners(scores, ScoreIterationListener(50, out=lambda s: None))
        s0 = model.score(x, y)
        model.fit((x, y), epochs=60)
        s1 = model.score(x, y)
        assert s1 < s0 * 0.5
        ev = model.evaluate((x, y))
        assert ev.accuracy() > 0.9
        assert len(scores.scores) == 60

    def test_minibatch_fit(self):
        x, y = two_moons(128)
        model = MultiLayerNetwork(self._mlp_conf()).init()
        model.fit((x, y), epochs=10, batch_size=32)
        assert model.iteration == 40

    def test_feed_forward_collects_activations(self):
        x, y = two_moons(8)
        model = MultiLayerNetwork(self._mlp_conf()).init()
        acts = model.feed_forward(x)
        assert len(acts) == 2
        assert acts[0].shape == (8, 16)
        assert acts[1].shape == (8, 2)

    def test_conf_json_roundtrip(self):
        conf = self._mlp_conf()
        j = conf.to_json()
        conf2 = MultiLayerConfiguration.from_json(j)
        assert conf2.layers == conf.layers
        assert conf2.input_type == conf.input_type
        assert conf2.updater == conf.updater

    def test_save_restore_identical_outputs(self):
        x, y = two_moons(64)
        model = MultiLayerNetwork(self._mlp_conf()).init()
        model.fit((x, y), epochs=3)
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "model.zip")
            save_network(model, p)
            m2 = restore_network(p)
        np.testing.assert_allclose(
            np.asarray(model.output(x)), np.asarray(m2.output(x)), rtol=1e-6
        )
        assert m2.iteration == model.iteration
        # continuing training works (updater state restored)
        m2.fit((x, y), epochs=1)

    def test_frozen_layer_does_not_update(self):
        x, y = two_moons(64)
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=8, activation="tanh", trainable=False),
                OutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.feed_forward(2),
            updater={"type": "sgd", "lr": 0.1},
        )
        model = MultiLayerNetwork(conf).init()
        w_before = np.asarray(model.params[0]["W"]).copy()
        model.fit((x, y), epochs=5)
        np.testing.assert_array_equal(w_before, np.asarray(model.params[0]["W"]))
        # output layer did move
        assert not np.allclose(0, np.asarray(model.params[1]["W"]) - 0)

    def test_batchnorm_state_updates(self):
        x, y = two_moons(64)
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=8, activation="identity"),
                BatchNorm(),
                OutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.feed_forward(2),
            updater={"type": "sgd", "lr": 0.1},
        )
        model = MultiLayerNetwork(conf).init()
        mean_before = np.asarray(model.state[1]["mean"]).copy()
        model.fit((x, y), epochs=2)
        assert not np.allclose(mean_before, np.asarray(model.state[1]["mean"]))

    def test_cnn_pipeline(self):
        rs = np.random.RandomState(0)
        x = rs.randn(16, 8, 8, 1).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 16)]
        conf = MultiLayerConfiguration(
            layers=(
                Conv2D(n_out=4, kernel=(3, 3), activation="relu"),
                Subsampling2D(kernel=(2, 2), stride=(2, 2)),
                OutputLayer(n_out=3, activation="softmax"),
            ),
            input_type=InputType.convolutional(8, 8, 1),
            updater={"type": "adam", "lr": 0.01},
        )
        model = MultiLayerNetwork(conf).init()
        s0 = model.score(x, y)
        model.fit((x, y), epochs=30)
        assert model.score(x, y) < s0
        assert model.output(x).shape == (16, 3)

    def test_dropout_train_vs_inference(self):
        x, _ = two_moons(32)
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=32, activation="tanh"),
                DropoutLayer(dropout=0.5),
                OutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.feed_forward(2),
        )
        model = MultiLayerNetwork(conf).init()
        o1 = np.asarray(model.output(x))
        o2 = np.asarray(model.output(x))
        np.testing.assert_array_equal(o1, o2)  # inference is deterministic


class TestRnnTraining:
    def _seq_data(self, n=16, t=12, f=3, k=2, seed=0):
        rs = np.random.RandomState(seed)
        x = rs.randn(n, t, f).astype(np.float32)
        # label: sign of running mean of first feature
        cum = np.cumsum(x[..., 0], axis=1) / np.arange(1, t + 1)
        lab = (cum > 0).astype(int)
        y = np.eye(k, dtype=np.float32)[lab]
        return x, y

    def test_lstm_sequence_classification(self):
        x, y = self._seq_data()
        conf = MultiLayerConfiguration(
            layers=(
                LSTM(n_out=8),
                RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"),
            ),
            input_type=InputType.recurrent(3, 12),
            updater={"type": "adam", "lr": 0.02},
        )
        model = MultiLayerNetwork(conf).init()
        s0 = model.score(x, y)
        model.fit((x, y), epochs=40)
        assert model.score(x, y) < s0 * 0.8

    def test_tbptt_runs_and_carries(self):
        x, y = self._seq_data(n=8, t=20)
        conf = MultiLayerConfiguration(
            layers=(
                LSTM(n_out=8),
                RnnOutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.recurrent(3, 20),
            updater={"type": "adam", "lr": 0.01},
            backprop_type="tbptt",
            tbptt_fwd_length=5,
        )
        model = MultiLayerNetwork(conf).init()
        model.fit((x, y), epochs=2)
        # 20 timesteps / 5 per chunk = 4 iterations per batch per epoch
        assert model.iteration == 8

    def test_rnn_time_step_matches_full_forward(self):
        x, _ = self._seq_data(n=4, t=6)
        conf = MultiLayerConfiguration(
            layers=(
                SimpleRnn(n_out=5),
                RnnOutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.recurrent(3, 6),
        )
        model = MultiLayerNetwork(conf).init()
        full = np.asarray(model.output(x))
        model.rnn_clear_previous_state()
        stepped = []
        for t in range(x.shape[1]):
            stepped.append(np.asarray(model.rnn_time_step(x[:, t, :])))
        stepped = np.stack(stepped, axis=1)
        np.testing.assert_allclose(full, stepped, rtol=1e-5, atol=1e-6)


class TestEvaluation:
    def test_evaluation_metrics(self):
        ev = Evaluation(num_classes=2)
        labels = np.array([[1, 0], [1, 0], [0, 1], [0, 1]])
        preds = np.array([[0.9, 0.1], [0.4, 0.6], [0.2, 0.8], [0.3, 0.7]])
        ev.eval(labels, preds)
        assert ev.accuracy() == pytest.approx(0.75)
        assert ev.confusion.count(0, 1) == 1
        assert 0 < ev.f1() <= 1
        assert "Accuracy" in ev.stats()

    def test_evaluation_merge(self):
        labels = np.eye(3)[np.array([0, 1, 2, 0])]
        preds = np.eye(3)[np.array([0, 1, 1, 0])] * 0.9 + 0.05
        e1, e2, e3 = Evaluation(3), Evaluation(3), Evaluation(3)
        e1.eval(labels[:2], preds[:2])
        e2.eval(labels[2:], preds[2:])
        e3.eval(labels, preds)
        e1.merge(e2)
        assert np.array_equal(e1.confusion.matrix, e3.confusion.matrix)

    def test_regression_evaluation(self):
        ev = RegressionEvaluation()
        y = np.array([[1.0], [2.0], [3.0]])
        p = np.array([[1.1], [1.9], [3.2]])
        ev.eval(y, p)
        assert ev.mean_squared_error() == pytest.approx(np.mean((y - p) ** 2), rel=1e-6)
        assert ev.pearson_correlation() > 0.99
        assert ev.r_squared() > 0.9

    def test_roc_auc_perfect_and_random(self):
        roc = ROC(num_bins=100)
        labels = np.array([0, 0, 1, 1])
        preds = np.array([0.1, 0.2, 0.8, 0.9])
        roc.eval(labels, preds)
        assert roc.calculate_auc() == pytest.approx(1.0, abs=0.02)
        roc2 = ROC(num_bins=0)
        roc2.eval(labels, preds)
        assert roc2.calculate_auc() == pytest.approx(1.0, abs=1e-6)

    def test_roc_merge_matches_single(self):
        rs = np.random.RandomState(0)
        labels = rs.randint(0, 2, 1000)
        preds = np.clip(labels * 0.3 + rs.uniform(0, 0.7, 1000), 0, 1)
        ra, rb, rall = ROC(50), ROC(50), ROC(50)
        ra.eval(labels[:500], preds[:500])
        rb.eval(labels[500:], preds[500:])
        rall.eval(labels, preds)
        ra.merge(rb)
        assert ra.calculate_auc() == pytest.approx(rall.calculate_auc(), abs=1e-9)

    def test_roc_multiclass(self):
        rs = np.random.RandomState(1)
        labels = rs.randint(0, 3, 300)
        preds = np.eye(3)[labels] * 0.6 + rs.dirichlet([1, 1, 1], 300) * 0.4
        roc = ROCMultiClass(100)
        roc.eval(labels, preds)
        assert roc.calculate_average_auc() > 0.9

    def test_evaluation_binary(self):
        ev = EvaluationBinary()
        labels = np.array([[1, 0], [1, 1], [0, 0], [0, 1]])
        preds = np.array([[0.9, 0.2], [0.8, 0.4], [0.3, 0.1], [0.2, 0.9]])
        ev.eval(labels, preds)
        assert ev.accuracy(0) == 1.0
        assert ev.recall(1) == pytest.approx(0.5)

    def test_calibration(self):
        rs = np.random.RandomState(2)
        p = rs.uniform(0, 1, (2000, 1))
        labels = (rs.uniform(size=(2000, 1)) < p).astype(float)
        labels2 = np.concatenate([1 - labels, labels], axis=1)
        preds = np.concatenate([1 - p, p], axis=1)
        ec = EvaluationCalibration()
        ec.eval(labels2, preds)
        assert ec.expected_calibration_error(1) < 0.05


class TestReviewRegressions:
    """Regressions for code-review findings (round 1)."""

    def test_conv_bn_conv_stack_builds_and_trains(self):
        rs = np.random.RandomState(0)
        x = rs.randn(8, 8, 8, 1).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 8)]
        conf = MultiLayerConfiguration(
            layers=(
                Conv2D(n_out=4, kernel=(3, 3), activation="relu"),
                BatchNorm(),
                Conv2D(n_out=4, kernel=(3, 3), activation="relu"),
                OutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.convolutional(8, 8, 1),
            updater={"type": "adam", "lr": 0.01},
        )
        model = MultiLayerNetwork(conf).init()
        # BN must be per-channel (4 channels), not flattened
        assert model.state[1]["mean"].shape == (4,)
        model.fit((x, y), epochs=2)
        assert model.output(x).shape == (8, 2)

    def test_subsampling1d_mask_propagation(self):
        from deeplearning4j_tpu.nn.layers import Subsampling1D

        rs = np.random.RandomState(1)
        x = rs.randn(2, 6, 3).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, (2, 3))]
        mask = np.array([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], np.float32)
        conf = MultiLayerConfiguration(
            layers=(
                Subsampling1D(kernel=2, stride=2),
                LSTM(n_out=4),
                RnnOutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.recurrent(3, 6),
        )
        model = MultiLayerNetwork(conf).init()
        # must not crash with mismatched scan lengths; mask shrinks 6 -> 3
        model.fit((x, y, mask), epochs=1)

    def test_wrapped_rnn_l2_counts(self):
        from deeplearning4j_tpu.nn.layers import Bidirectional

        rs = np.random.RandomState(2)
        x = rs.randn(2, 4, 3).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 2)]
        inner = LSTM(n_out=4, l2=0.05)
        from deeplearning4j_tpu.nn.layers import LastTimeStep

        conf = MultiLayerConfiguration(
            layers=(
                LastTimeStep(rnn=inner),
                OutputLayer(n_out=2, activation="softmax"),
            ),
            input_type=InputType.recurrent(3, 4),
        )
        model = MultiLayerNetwork(conf).init()
        pen = float(model.layers[0].regularization_penalty(model.params[0]))
        assert pen > 0.0  # inner LSTM's l2 is not silently dropped


class TestRnnInputProjectionHoist:
    """Round-3 TPU optimization: the input projection is computed for all
    timesteps in ONE matmul before the scan. Must be numerically identical
    to the per-step cell path (masking and peepholes included)."""

    @pytest.mark.parametrize("cls_name", ["LSTM", "GravesLSTM", "SimpleRnn"])
    def test_fast_path_matches_cell_path(self, cls_name):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn import layers as L

        cls = getattr(L, cls_name)
        layer = cls(n_out=8)
        rs = np.random.RandomState(0)
        p = layer.init(jax.random.PRNGKey(0), InputType.recurrent(5, 12))
        if "peephole" in p:
            p = dict(p)
            p["peephole"] = jnp.asarray(rs.randn(24).astype(np.float32) * 0.3)
        x = jnp.asarray(rs.randn(4, 12, 5).astype(np.float32))
        mask = jnp.asarray((rs.rand(4, 12) > 0.3).astype(np.float32))
        carry = layer.initial_carry(4, jnp.float32)
        y_fast, c_fast = layer.apply_seq(p, x, carry, mask)
        orig = cls._input_proj
        try:
            # disable only the WHOLE-SEQUENCE (3-D) projection: apply_seq
            # then falls back to per-step _cell, which still projects rows
            cls._input_proj = lambda self, params, xx: (
                None if xx.ndim == 3 else orig(self, params, xx))
            y_slow, c_slow = layer.apply_seq(
                p, x, layer.initial_carry(4, jnp.float32), mask)
        finally:
            cls._input_proj = orig
        np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_slow),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(c_fast),
                        jax.tree_util.tree_leaves(c_slow)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestChainedFit:
    """Round-5 (VERDICT r4 #9): fit() chains K steps per dispatch for
    small rng-free models — identical math to the per-step path."""

    @staticmethod
    def _conf():
        return MultiLayerConfiguration(
            layers=(Dense(n_out=10, activation="tanh"),
                    OutputLayer(n_out=3, activation="softmax")),
            input_type=InputType.feed_forward(4),
            updater={"type": "adam", "lr": 0.01}, seed=5)

    def test_chained_equals_per_step_exactly(self):
        import os
        rs = np.random.RandomState(0)
        x = rs.rand(64, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]

        old = os.environ.get("DL4J_TPU_CHAIN_STEPS")
        try:
            os.environ["DL4J_TPU_CHAIN_STEPS"] = "0"
            m_ref = MultiLayerNetwork(self._conf()).init()
            m_ref.fit((x, y), epochs=4, batch_size=8)   # 8 batches/epoch
            os.environ["DL4J_TPU_CHAIN_STEPS"] = "4"
            m_ch = MultiLayerNetwork(self._conf()).init()
            m_ch.fit((x, y), epochs=4, batch_size=8)
        finally:
            if old is None:
                os.environ.pop("DL4J_TPU_CHAIN_STEPS", None)
            else:
                os.environ["DL4J_TPU_CHAIN_STEPS"] = old
        assert m_ch.iteration == m_ref.iteration == 32
        for a, b in zip(jax.tree_util.tree_leaves(m_ch.params),
                        jax.tree_util.tree_leaves(m_ref.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_auto_chain_skips_dropout_models(self):
        conf = MultiLayerConfiguration(
            layers=(Dense(n_out=8, activation="tanh", dropout=0.5),
                    OutputLayer(n_out=3, activation="softmax")),
            input_type=InputType.feed_forward(4), seed=1)
        m = MultiLayerNetwork(conf).init()
        assert m._chain_k() == 0      # randomness -> per-step stream kept

    def test_auto_chain_enables_for_small_rng_free(self):
        m = MultiLayerNetwork(self._conf()).init()
        assert m._chain_k() == 8

    def test_uneven_tail_still_trains(self):
        rs = np.random.RandomState(2)
        x = rs.rand(30, 4).astype(np.float32)   # 3 full batches + tail of 6
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 30)]
        m = MultiLayerNetwork(self._conf()).init()
        s0 = m.score(x, y)
        m.fit((x, y), epochs=6, batch_size=8)
        assert m.iteration == 6 * 4
        assert m.score(x, y) < s0

    def test_auto_chain_skips_all_noise_layers(self):
        from deeplearning4j_tpu.nn.layers.core import (
            GaussianDropout, GaussianNoise)
        from deeplearning4j_tpu.nn.layers.recurrent import Bidirectional, SimpleRnn

        for noisy in (GaussianNoise(stddev=0.1), GaussianDropout(rate=0.3)):
            conf = MultiLayerConfiguration(
                layers=(Dense(n_out=8), noisy,
                        OutputLayer(n_out=3, activation="softmax")),
                input_type=InputType.feed_forward(4), seed=1)
            assert MultiLayerNetwork(conf).init()._chain_k() == 0, type(noisy)
        # wrapper with a dropout-carrying inner rnn
        conf = MultiLayerConfiguration(
            layers=(Bidirectional(rnn=SimpleRnn(n_out=4, dropout=0.2)),
                    Dense(n_out=4),
                    OutputLayer(n_out=2, activation="softmax")),
            input_type=InputType.recurrent(3, 5), seed=1)
        assert MultiLayerNetwork(conf).init()._chain_k() == 0


# ---------------------------------------------------------------------------
# Gradient accumulation inside the step (DL4J_TPU_GRAD_ACCUM,
# nn/step_program.py): parity with the un-accumulated step
# ---------------------------------------------------------------------------


def _mln(seed=3, updater=None):
    conf = MultiLayerConfiguration(
        layers=(Dense(n_out=16, activation="tanh"),
                OutputLayer(n_out=3, activation="softmax")),
        input_type=InputType.feed_forward(8),
        updater=updater or {"type": "adam", "lr": 0.01},
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _cg(seed=3):
    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .add_layer("d", Dense(n_out=16, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "d")
            .set_outputs("out")
            .updater({"type": "sgd", "lr": 0.1})
            .build())
    g = ComputationGraph(conf)
    g.init()
    return g


def _data(n=32, seed=0, feat=8, classes=3):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, feat).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, n)]
    return x, y


def _leaves(m):
    import jax

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(m.params)]


class TestGradAccumParity:
    """Accumulated step ≡ full-batch step in fp32 (equal-size micro-batches,
    mean-of-micro-means == full mean exactly). Models here carry no
    batch-coupled layers: BatchNorm statistics over 8-row micro-batches
    genuinely differ from 32-row full-batch statistics — that is the
    documented semantic of accumulation, not a parity bug."""

    @pytest.fixture(autouse=True)
    def _per_step_dispatch(self, monkeypatch):
        # parity must compare the same dispatch shape; chaining is its own knob
        monkeypatch.setenv("DL4J_TPU_CHAIN_STEPS", "0")
        monkeypatch.delenv("DL4J_TPU_GRAD_ACCUM", raising=False)

    def _fit(self, model, data, steps=3):
        for _ in range(steps):
            model.fit([data], epochs=1)
        return _leaves(model)

    @pytest.mark.parametrize("updater", [
        {"type": "sgd", "lr": 0.1},
        {"type": "adam", "lr": 0.01},
    ])
    def test_mln_parity(self, updater, monkeypatch):
        data = _data(n=32)
        base = self._fit(_mln(seed=5, updater=updater), data)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(_mln(seed=5, updater=updater), data)
        for a, b in zip(base, accum):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_cg_parity(self, monkeypatch):
        data = _data(n=32)
        base = self._fit(_cg(seed=5), data)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(_cg(seed=5), data)
        for a, b in zip(base, accum):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_dp_compressed_parity(self, monkeypatch):
        """Accumulation inside the donated step composes with the DP
        explicit-exchange compressed arm: micro-grads are averaged BEFORE
        the exchange, so the threshold codec sees the same mean gradient."""
        from deeplearning4j_tpu.parallel import (MeshSpec, ParallelWrapper,
                                                 make_mesh)

        data = _data(n=64)
        m1 = _mln(seed=5, updater={"type": "sgd", "lr": 0.1})
        ParallelWrapper(m1, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True,
                        compress_threshold=1e-3).fit(data, epochs=3)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "2")
        m2 = _mln(seed=5, updater={"type": "sgd", "lr": 0.1})
        ParallelWrapper(m2, mesh=make_mesh(MeshSpec(data=8)),
                        grad_compress=True,
                        compress_threshold=1e-3).fit(data, epochs=3)
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)

    def test_non_divisible_batch_falls_back_with_warning(self, monkeypatch):
        from deeplearning4j_tpu.nn import step_program

        # the warn-once flag lives in the unified step-program module now
        monkeypatch.setattr(step_program, "_GRAD_ACCUM_WARNED", False)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "5")
        data = _data(n=32)  # 32 % 5 != 0
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            accum = self._fit(_mln(seed=5), data, steps=1)
        assert any("DL4J_TPU_GRAD_ACCUM" in str(w.message) for w in caught)
        # the fallback is the plain un-accumulated step, bit for bit
        monkeypatch.delenv("DL4J_TPU_GRAD_ACCUM")
        base = self._fit(_mln(seed=5), data, steps=1)
        for a, b in zip(base, accum):
            np.testing.assert_array_equal(a, b)

    def test_accum_is_engaged_not_vacuous(self, monkeypatch):
        """The accum=4 arm must actually run the scan path: its BN-free
        params match, but a model WITH BatchNorm must differ — proving the
        micro-batch semantics (and thus the scan) are live."""
        from deeplearning4j_tpu.nn.layers import BatchNorm

        def bn_model(seed=5):
            conf = MultiLayerConfiguration(
                layers=(Dense(n_out=16, activation="tanh"),
                        BatchNorm(),
                        OutputLayer(n_out=3, activation="softmax")),
                input_type=InputType.feed_forward(8),
                updater={"type": "sgd", "lr": 0.1},
                seed=seed,
            )
            return MultiLayerNetwork(conf).init()

        data = _data(n=32)
        base = self._fit(bn_model(), data, steps=2)
        monkeypatch.setenv("DL4J_TPU_GRAD_ACCUM", "4")
        accum = self._fit(bn_model(), data, steps=2)
        deltas = [np.max(np.abs(a - b)) for a, b in zip(base, accum)]
        assert max(deltas) > 1e-7
