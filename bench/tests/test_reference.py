"""The plain reference agrees with the program's LM, and the on-device
generators with the program's host ones, at a tiny size on the CPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, data, stack
from bench.tests.helpers import HERE


def tiny(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    mix = json.loads((HERE / "traffic" /
                      ("tiny-qa.json" if "ralm" in name else "tiny-lm.json")).read_text())
    return cfg, mix


@pytest.mark.parametrize("name", ["tiny-ralm", "tiny-knnlm"])
def test_reference_matches_prefill_and_decode(name):
    cfg, mix = tiny(name)
    sysm = stack.build(cfg, mix, seed=3)
    ref = stack.reference(cfg)
    model, params = sysm.stack.model, sysm.params
    toks = np.random.default_rng(0).integers(2, cfg["vocab_size"], 20)
    last, state, pos = model.prefill(params, jnp.asarray(toks[:12])[None],
                                     window_cache=64)
    got = [np.asarray(last[0])]
    for t in toks[12:19]:
        lg, state = model.decode_step(params, state, jnp.asarray([t]), pos)
        pos = pos + 1
        got.append(np.asarray(lg[0]))
    stated = check.stated_precision(cfg)
    h = ref.hidden(params, jnp.asarray(toks[None, :19]), jnp.asarray([12]),
                   ref.model_cfg(cfg), stated)
    want = np.asarray(ref.logits_at(params, h[0, 11:19],
                                    np.arange(11, 19) < 12, stated))
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-4, atol=2e-4)


def test_encode_matches_program_encoder():
    from repro.retrieval.encoder import ContextEncoder
    from bench.references.dense_decoder import encode
    enc = ContextEncoder(300, d=16, window=32)
    toks = list(range(5, 60))
    np.testing.assert_array_equal(encode(toks, enc.table, 32, 0.95),
                                  enc.encode(toks))


def test_device_keys_match_program_host_builders():
    from repro.retrieval.encoder import ContextEncoder
    from repro.retrieval.kb import DenseKB, build_knn_datastore
    enc = ContextEncoder(200, d=16, window=16)
    docs = data.passages(jax.random.PRNGKey(1), jax.random.PRNGKey(2), n=50,
                         length=24, vocab=200, n_topics=4, topic_words=8,
                         topical_share=0.8)
    host = DenseKB.build(np.asarray(docs).tolist(), enc).embeddings
    dev = data.passage_keys(docs, jnp.asarray(enc.table))
    np.testing.assert_allclose(np.asarray(dev), host, atol=1e-6)
    stream = np.asarray(docs).reshape(-1)
    ds = build_knn_datastore(stream, enc, context=16, limit=300)
    keys = data.fir_keys(jnp.asarray(stream), jnp.asarray(enc.table), 0.95,
                         n_entries=300, window=16)
    np.testing.assert_allclose(np.asarray(keys), ds.embeddings, atol=1e-5)
    np.testing.assert_array_equal(stream[16:316], ds.values)


def test_passages_follow_the_topic_model():
    docs = np.asarray(data.passages(jax.random.PRNGKey(1), jax.random.PRNGKey(2),
                                    n=64, length=40, vocab=1000, n_topics=4,
                                    topic_words=8, topical_share=0.8))
    assert docs.min() >= 2 and docs.max() < 1000
    words = [set(docs[i]) for i in range(64)]
    same = len(words[0] & words[1])       # consecutive docs share a topic
    far = len(words[0] & words[63])       # first and last do not
    assert same > far
