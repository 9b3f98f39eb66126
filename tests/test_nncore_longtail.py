"""nn-core long tail (VERDICT round-1 item 8): constraints, DropConnect,
LBFGS/CG/line-search solvers, memory_report, word-vector serialization,
BoW/TF-IDF."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration, MultiLayerNetwork


def _data(n=48, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, n)]
    return x, y


class TestConstraints:
    def _fit(self, constraints, lr=0.5):
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=16, activation="tanh", constraints=constraints),
                OutputLayer(n_out=3, activation="softmax"),
            ),
            input_type=InputType.feed_forward(6),
            updater={"type": "sgd", "lr": lr},
            seed=0,
        )
        m = MultiLayerNetwork(conf).init()
        m.fit(_data(), epochs=5)
        return np.asarray(m.params[0]["W"]), np.asarray(m.params[0]["b"])

    def test_max_norm_enforced_inside_step(self):
        W, _ = self._fit(({"type": "max_norm", "max_norm": 0.5},), lr=2.0)
        col_norms = np.linalg.norm(W, axis=0)
        assert np.all(col_norms <= 0.5 + 1e-5)

    def test_unit_norm(self):
        W, _ = self._fit(({"type": "unit_norm"},))
        np.testing.assert_allclose(np.linalg.norm(W, axis=0), 1.0, atol=1e-4)

    def test_non_negative(self):
        W, b = self._fit(({"type": "non_negative"},))
        assert np.all(W >= 0)
        # bias untouched by default (apply_to_biases=False)
        assert b.shape == (16,)

    def test_min_max_norm(self):
        W, _ = self._fit(({"type": "min_max_norm", "min_norm": 0.3, "max_norm": 0.6},))
        col_norms = np.linalg.norm(W, axis=0)
        assert np.all(col_norms >= 0.3 - 1e-4) and np.all(col_norms <= 0.6 + 1e-4)

    def test_constraint_serde_roundtrip(self):
        layer = Dense(n_out=4, constraints=({"type": "max_norm", "max_norm": 1.5},))
        from deeplearning4j_tpu.nn.config import LayerConfig

        again = LayerConfig.from_json(layer.to_json())
        assert tuple(again.constraints) == tuple(layer.constraints)


class TestDropConnect:
    def test_dropconnect_trains_and_is_deterministic_at_inference(self):
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=16, activation="tanh",
                      weight_noise={"type": "dropconnect", "p": 0.9}),
                OutputLayer(n_out=3, activation="softmax"),
            ),
            input_type=InputType.feed_forward(6),
            updater={"type": "adam", "lr": 0.05},
            seed=0,
        )
        m = MultiLayerNetwork(conf).init()
        x, y = _data()
        s0 = m.score(x, y)
        m.fit((x, y), epochs=15)
        assert m.score(x, y) < s0
        o1, o2 = np.asarray(m.output(x)), np.asarray(m.output(x))
        np.testing.assert_array_equal(o1, o2)  # no noise at inference

    def test_gaussian_weight_noise_changes_train_loss_only(self):
        layer = Dense(n_out=8, n_in=6,
                      weight_noise={"type": "gaussian", "stddev": 0.5})
        params = layer.init(jax.random.PRNGKey(0), InputType.feed_forward(6))
        noisy = layer.maybe_weight_noise(params, True, jax.random.PRNGKey(1))
        assert not np.allclose(np.asarray(noisy["W"]), np.asarray(params["W"]))
        # bias untouched by default
        np.testing.assert_array_equal(np.asarray(noisy["b"]), np.asarray(params["b"]))
        same = layer.maybe_weight_noise(params, False, jax.random.PRNGKey(1))
        assert same is params


class TestSolvers:
    def _model(self, algo):
        conf = MultiLayerConfiguration(
            layers=(
                Dense(n_out=12, activation="tanh"),
                OutputLayer(n_out=3, activation="softmax"),
            ),
            input_type=InputType.feed_forward(6),
            optimization_algo=algo,
            solver_iterations=30,
            seed=0,
        )
        return MultiLayerNetwork(conf).init()

    @pytest.mark.parametrize("algo", ["lbfgs", "conjugate_gradient",
                                      "line_gradient_descent"])
    def test_solver_reduces_loss(self, algo):
        m = self._model(algo)
        x, y = _data()
        s0 = m.score(x, y)
        m.fit((x, y), epochs=1)
        s1 = m.score(x, y)
        assert s1 < s0 * 0.8, f"{algo}: {s0} -> {s1}"

    def test_lbfgs_beats_gd_on_quadratic(self):
        """L-BFGS must converge much further than plain line-search GD in the
        same step budget on an ill-conditioned quadratic."""
        from deeplearning4j_tpu.train.solvers import BackTrackLineSearch, Solver

        rs = np.random.RandomState(0)
        scales = jnp.asarray(np.logspace(0, 2, 20).astype(np.float32))
        target = jnp.asarray(rs.randn(20).astype(np.float32))

        class Toy:
            dtype = jnp.float32
            params = {"w": jnp.zeros(20, jnp.float32)}
            state = ()

            def _loss(self, params, state, x, y, fm, lm, rngs, train=False):
                w = params["w"]
                return jnp.sum(scales * (w - target) ** 2), state

        toy1, toy2 = Toy(), Toy()
        l_lbfgs = Solver(toy1, "lbfgs").optimize((np.zeros((1, 1)), None), iterations=40)
        l_gd = Solver(toy2, "line_gradient_descent").optimize(
            (np.zeros((1, 1)), None), iterations=40)
        assert l_lbfgs < l_gd * 0.01

    def test_solver_algo_serde(self):
        conf = MultiLayerConfiguration(
            layers=(OutputLayer(n_out=2),), input_type=InputType.feed_forward(3),
            optimization_algo="lbfgs", solver_iterations=7,
        )
        again = MultiLayerConfiguration.from_json(conf.to_json())
        assert again.optimization_algo == "lbfgs" and again.solver_iterations == 7


class TestMemoryReport:
    def test_report_contains_compiled_footprint(self):
        from deeplearning4j_tpu.nn.memory import memory_report

        conf = MultiLayerConfiguration(
            layers=(Dense(n_out=32, activation="relu"),
                    OutputLayer(n_out=10, activation="softmax")),
            input_type=InputType.feed_forward(20),
            updater={"type": "adam", "lr": 1e-3},
        )
        m = MultiLayerNetwork(conf).init()
        rep = memory_report(m, batch_size=16)
        # params: (20*32+32) + (32*10+10) floats
        assert rep.params_bytes == ((20 * 32 + 32) + (32 * 10 + 10)) * 4
        # adam keeps 2 moments per param
        assert rep.opt_state_bytes >= 2 * rep.params_bytes
        assert rep.total_training_bytes() > rep.params_bytes
        text = rep.to_string()
        assert "MemoryReport" in text and "training" in text


class TestWordVectorSerializer:
    def _model(self):
        from deeplearning4j_tpu.nlp.embeddings import Word2Vec

        sents = [["the", "quick", "brown", "fox"], ["the", "lazy", "dog"],
                 ["the", "fox", "and", "the", "dog"]] * 4
        return Word2Vec(layer_size=12, min_word_frequency=1, epochs=2,
                        seed=1).fit(sents)

    def test_text_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.nlp.serializer import WordVectorSerializer

        m = self._model()
        p = str(tmp_path / "vecs.txt")
        WordVectorSerializer.write_word_vectors(m, p)
        back = WordVectorSerializer.load_txt_vectors(p)
        for w in ("the", "fox", "dog"):
            np.testing.assert_allclose(back.get_word_vector(w),
                                       m.get_word_vector(w), rtol=1e-4, atol=1e-5)

    def test_binary_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.nlp.serializer import WordVectorSerializer

        m = self._model()
        p = str(tmp_path / "vecs.bin")
        WordVectorSerializer.write_binary(m, p)
        back = WordVectorSerializer.read_binary(p)
        for w in ("the", "quick", "lazy"):
            np.testing.assert_allclose(back.get_word_vector(w),
                                       m.get_word_vector(w), rtol=1e-6)
        assert back.similarity("fox", "dog") == pytest.approx(
            m.similarity("fox", "dog"), abs=1e-5)

    def test_zip_roundtrip_preserves_counts(self, tmp_path):
        from deeplearning4j_tpu.nlp.serializer import WordVectorSerializer

        m = self._model()
        p = str(tmp_path / "w2v.zip")
        WordVectorSerializer.write_word2vec_model(m, p)
        back = WordVectorSerializer.read_word2vec_model(p)
        np.testing.assert_allclose(back.syn0, m.syn0, rtol=1e-6)
        assert back.vocab.word_for("the").count == m.vocab.word_for("the").count


class TestVectorizers:
    DOCS = ["the cat sat on the mat", "the dog sat", "cats and dogs and cats"]

    def test_bow_counts(self):
        from deeplearning4j_tpu.nlp.vectorizers import BagOfWordsVectorizer

        v = BagOfWordsVectorizer(min_word_frequency=1)
        m = v.fit_transform(self.DOCS)
        assert m.shape == (3, v.vocab_size)
        the = v.vocab.index_of("the")
        assert m[0, the] == 2.0 and m[1, the] == 1.0 and m[2, the] == 0.0

    def test_tfidf_downweights_common_terms(self):
        from deeplearning4j_tpu.nlp.vectorizers import TfidfVectorizer

        v = TfidfVectorizer(min_word_frequency=1)
        m = v.fit_transform(self.DOCS)
        the, cat = v.vocab.index_of("the"), v.vocab.index_of("cat")
        # 'the' (2 docs) carries lower idf than 'cat' (1 doc)
        assert v.idf[the] < v.idf[cat]
        assert m.shape == (3, v.vocab_size)

    def test_vectorize_to_dataset_pair(self):
        from deeplearning4j_tpu.nlp.vectorizers import BagOfWordsVectorizer

        v = BagOfWordsVectorizer().fit(self.DOCS)
        x, y = v.vectorize("the cat", "pets", ["pets", "other"])
        assert x.shape == (v.vocab_size,)
        np.testing.assert_array_equal(y, [1.0, 0.0])


class TestViterbiAndMovingWindow:
    def test_viterbi_smooths_isolated_flips(self):
        from deeplearning4j_tpu.utils.misc import Viterbi
        v = Viterbi(states=2, meta_stability=0.95, p_correct=0.9)
        noisy = np.array([0, 0, 0, 1, 0, 0, 1, 1, 1, 1, 0, 1, 1])
        score, smoothed = v.decode(noisy)
        np.testing.assert_array_equal(
            smoothed, [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1])
        assert np.isfinite(score)

    def test_viterbi_one_hot_input_and_validation(self):
        from deeplearning4j_tpu.utils.misc import Viterbi
        import pytest
        v = Viterbi(states=3)
        oh = np.eye(3)[[0, 0, 2, 2]]
        _, path = v.decode(oh)
        assert path.shape == (4,)
        with pytest.raises(ValueError, match="out of range"):
            v.decode(np.array([0, 5]))
        with pytest.raises(ValueError):
            Viterbi(states=1)

    def test_moving_window_matrix(self):
        from deeplearning4j_tpu.utils.misc import MovingWindowMatrix
        m = np.arange(12).reshape(3, 4)
        ws = MovingWindowMatrix(m, 2, 2).window_list()
        assert len(ws) == 2 * 3
        np.testing.assert_array_equal(ws[0], [[0, 1], [4, 5]])
        ws_rot = MovingWindowMatrix(m, 2, 2, add_rotate=True).window_list()
        assert len(ws_rot) == 2 * 3 * 4
        import pytest
        with pytest.raises(ValueError, match="exceeds"):
            MovingWindowMatrix(m, 5, 2)


class TestMemoryReportCG:
    def test_cg_memory_report(self):
        """NetworkMemoryReport covers ComputationGraph too (round 5):
        multi-input DAG compiles and reports exact executable footprints."""
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration, MergeVertex)
        from deeplearning4j_tpu.nn.input_type import InputType
        from deeplearning4j_tpu.nn.layers.core import Dense, OutputLayer
        from deeplearning4j_tpu.nn.memory import memory_report

        conf = (ComputationGraphConfiguration.builder()
                .add_inputs("a", "b")
                .set_input_types(InputType.feed_forward(3),
                                 InputType.feed_forward(5))
                .add_layer("da", Dense(n_out=6, activation="relu"), "a")
                .add_layer("db", Dense(n_out=6, activation="relu"), "b")
                .add_vertex("m", MergeVertex(), "da", "db")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "m")
                .set_outputs("out")
                .updater({"type": "adam", "lr": 1e-3})
                .build())
        m = ComputationGraph(conf).init()
        rep = memory_report(m, batch_size=8)
        assert rep.model_class == "ComputationGraph"
        assert rep.params_bytes > 0 and rep.opt_state_bytes > 0
        assert rep.total_training_bytes() >= rep.params_bytes
        assert "MemoryReport" in rep.to_string()


class TestCompileCache:
    def test_env_set_is_left_untouched(self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set -> JAX reads it; the function
        touches no config and creates nothing."""
        import jax

        from deeplearning4j_tpu.utils import compile_cache as cc

        def no_update(*a, **kw):
            raise AssertionError("jax.config.update called with the env set")

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xc"))
        monkeypatch.setattr(jax.config, "update", no_update)
        assert cc.enable_compilation_cache() == str(tmp_path / "xc")
        assert not (tmp_path / "xc").exists()

    def test_unset_means_checkout_cache(self, monkeypatch):
        """Unset -> <checkout>/.jax_cache, derived from the package's own
        location (the directory is part of the cache key: never ~, a temp
        name, a pid or a time)."""
        import jax

        from deeplearning4j_tpu.utils import compile_cache as cc

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        seen = {}
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        assert cc.enable_compilation_cache() == cc.CHECKOUT_CACHE_DIR
        assert seen == {"jax_compilation_cache_dir": cc.CHECKOUT_CACHE_DIR}
        assert os.path.isdir(cc.CHECKOUT_CACHE_DIR)
