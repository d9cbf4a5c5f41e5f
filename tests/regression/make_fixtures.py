"""Generate the committed checkpoint regression fixtures
(ref: deeplearning4j-core regressiontest/RegressionTest071.java — the
reference pins saved-model compatibility across releases with committed
model zips; these pin the round-3 checkpoint format for every later
round).

Run from the repo root on the CPU backend:

    JAX_PLATFORMS=cpu python tests/regression/make_fixtures.py

Regenerating is a FORMAT BREAK — only do it deliberately, alongside a
loader shim for the old format, and say so in the commit message.
"""

import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"  # fixtures are CPU-made, f32

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))

SEED = 20260729


def probe_batch():
    rng = np.random.default_rng(SEED)
    return rng.normal(size=(4, 4)).astype(np.float32)


def make_mln():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.normalizers import NormalizerStandardize
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.serialization import write_model

    rng = np.random.default_rng(SEED)
    x = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    conf = (NeuralNetConfiguration.builder().seed(SEED)
            .learning_rate(0.05).updater("adam")
            .regularization(True).l2(1e-4)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    for _ in range(3):
        net.fit(x, y)
    norm = NormalizerStandardize().fit(DataSet(x, y))
    write_model(net, HERE / "mln_071.zip", save_updater=True, normalizer=norm)
    return net


def make_cg():
    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ElementWiseVertex, GraphBuilder)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.serialization import write_model

    rng = np.random.default_rng(SEED + 1)
    x = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    g = GlobalConf(seed=SEED, learning_rate=0.05, updater="rmsprop")
    conf = (GraphBuilder(g)
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_in=4, n_out=8, activation="relu"), "in")
            .add_layer("d2", DenseLayer(n_in=4, n_out=8, activation="tanh"), "in")
            .add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
            .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                          activation="softmax",
                                          loss="mcxent"), "add")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    for _ in range(3):
        net.fit(x, y)
    write_model(net, HERE / "cg_071.zip", save_updater=True)
    return net


def make_word_vectors():
    from deeplearning4j_tpu.embeddings.serializer import WordVectorSerializer
    from deeplearning4j_tpu.embeddings.word2vec import Word2Vec
    from deeplearning4j_tpu.text.sentence_iterators import (
        CollectionSentenceIterator)

    rng = np.random.default_rng(SEED + 2)
    vocab = [f"tok{i}" for i in range(30)]
    sents = [" ".join(rng.choice(vocab, size=8)) for _ in range(200)]
    w2v = (Word2Vec.Builder()
           .iterate(CollectionSentenceIterator(sents))
           .layer_size(16).window_size(3).negative_sample(3)
           .use_hierarchic_softmax(False)
           .min_word_frequency(1).epochs(1).seed(SEED)
           .build())
    w2v.build_vocab()
    w2v.fit()
    WordVectorSerializer.write_word2vec_model(w2v, str(HERE / "w2v_071.zip"))
    return w2v


def main():
    (HERE).mkdir(parents=True, exist_ok=True)
    mln = make_mln()
    cg = make_cg()
    w2v = make_word_vectors()

    # record probe outputs so future rounds check numerics, not just loads
    x = probe_batch()
    expected = {
        "mln_output": np.asarray(mln.output(x)).tolist(),
        "cg_output": np.asarray(cg.output(x)[0]).tolist(),
        "mln_params_sha": _sha(np.asarray(mln.params())),
        "cg_params_sha": _sha(np.asarray(cg.params())),
        "w2v_words": sorted(w2v.vocab.words())[:5],
    }
    (HERE / "expected.json").write_text(json.dumps(expected, indent=2))
    print("fixtures written to", HERE)


def _sha(arr: np.ndarray) -> str:
    import hashlib
    return hashlib.sha256(np.ascontiguousarray(arr, np.float32).tobytes()
                          ).hexdigest()


if __name__ == "__main__":
    main()
