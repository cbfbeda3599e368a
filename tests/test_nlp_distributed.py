"""Multi-host SequenceVectors (the dl4j-spark-nlp Word2Vec role):
2-process subprocess run must converge to single-process semantic
quality, with bit-identical tables across processes after the final
rendezvous (spark/models/embeddings/word2vec/Word2Vec.java)."""

import json
import os
import subprocess
import sys

import numpy as np

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "w2v_distributed_worker.py")


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    return env


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _launch(nprocs, out_dir, extra=()):
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, HELPER, str(pid), str(nprocs), str(port),
         out_dir, *extra],
        env=_worker_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for pid in range(nprocs)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    return outs


def _cluster_quality(syn0, words):
    """Mean intra-cluster minus inter-cluster cosine similarity of the
    a*/b* word groups (higher = better separation)."""
    idx = {w: i for i, w in enumerate(words)}
    A = np.stack([syn0[idx[f"a{i}"]] for i in range(12)])
    B = np.stack([syn0[idx[f"b{i}"]] for i in range(12)])

    def cos(m1, m2):
        n1 = m1 / np.linalg.norm(m1, axis=1, keepdims=True)
        n2 = m2 / np.linalg.norm(m2, axis=1, keepdims=True)
        return (n1 @ n2.T).mean()

    return (cos(A, A) + cos(B, B)) / 2 - cos(A, B)


def _single_process_quality(epochs=6):
    sys.path.insert(0, os.path.dirname(HELPER))
    import w2v_distributed_worker as w

    from deeplearning4j_tpu.nlp.sequence_vectors import SequenceVectors

    sv = SequenceVectors(layer_size=16, window=3, negative=4,
                         epochs=epochs, seed=11, mode="scan")
    seqs = w.corpus()
    sv.build_vocab(seqs)
    sv.fit(seqs)
    return sv


def test_two_process_w2v_matches_single_quality(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("w2v_dist"))
    _launch(2, out)
    s0 = np.load(os.path.join(out, "syn0_0.npy"))
    s1 = np.load(os.path.join(out, "syn0_1.npy"))
    # after the final rendezvous both processes hold the same tables
    np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-7)

    sv = _single_process_quality()
    words = [sv.vocab.word_at_index(i) for i in range(sv.vocab.num_words())]
    q_dist = _cluster_quality(s0, words)
    q_single = _cluster_quality(sv.syn0, words)
    # distributed training reaches comparable semantic separation
    assert q_single > 0.3, f"oracle failed to separate: {q_single}"
    assert q_dist > 0.7 * q_single, (q_dist, q_single)


def test_two_process_w2v_threshold_compression(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("w2v_comp"))
    _launch(2, out, ("--threshold", "5e-3", "--epochs", "6",
                     "--sync-every", "2"))
    s0 = np.load(os.path.join(out, "syn0_0.npy"))
    s1 = np.load(os.path.join(out, "syn0_1.npy"))
    np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-7)
    stats = json.load(open(os.path.join(out, "stats_0.json")))
    assert stats["rendezvous"] == 3
    # compression actually engaged
    assert 0.0 < stats["compression_ratio"] < 1.0

    sv = _single_process_quality()
    words = [sv.vocab.word_at_index(i) for i in range(sv.vocab.num_words())]
    q = _cluster_quality(s0, words)
    assert q > 0.2, f"compressed run lost semantic separation: {q}"


def test_shard_sequences_partition():
    from deeplearning4j_tpu.nlp.distributed import (
        DistributedSequenceVectors,
    )

    seqs = [[str(i)] for i in range(7)]
    p0 = DistributedSequenceVectors.shard_sequences(seqs, 0, 2)
    p1 = DistributedSequenceVectors.shard_sequences(seqs, 1, 2)
    assert [s[0] for s in p0] == ["0", "2", "4", "6"]
    assert [s[0] for s in p1] == ["1", "3", "5"]
    assert len(p0) + len(p1) == 7
