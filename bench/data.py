"""Corpora and knowledge bases made on the device from the seed.

The distributions are those of the program's host generators
(``training.data.synthetic_corpus``, ``retrieval.encoder.ContextEncoder``,
``retrieval.kb.build_knn_datastore``), vectorised so that a KB of a million
rows is made in seconds instead of a per-document Python loop:

  * a topic model: ``n_topics`` topics of ``topic_words`` word ids each;
    each token of a passage of topic t is, with probability
    ``topical_share``, one of t's words and otherwise uniform over
    [2, vocab) (ids 0 and 1 are reserved); consecutive passages share
    topics;
  * the encoder table: one unit-norm Gaussian row per vocabulary id;
  * a passage key: the normalised mean of its tokens' table rows (the
    DPR-style document tower of ``ContextEncoder.encode_doc``);
  * a datastore key: the decayed FIR over the ``window`` tokens ending at
    the entry (``ContextEncoder.encode`` with that window), value = the next
    token of the stream."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

RESERVED = 2


@partial(jax.jit, static_argnames=("vocab", "dim"))
def encoder_table(key, vocab: int, dim: int):
    t = jax.random.normal(key, (vocab, dim), jnp.float32)
    return t / jnp.linalg.norm(t, axis=1, keepdims=True)


@partial(jax.jit, static_argnames=("n", "length", "vocab", "n_topics",
                                   "topic_words", "topical_share"))
def passages(topic_key, key, n: int, length: int, vocab: int, n_topics: int,
             topic_words: int, topical_share: float):
    """(n, length) int32 passages of the topic model ``topic_key`` names
    (the same key gives the same topics, so a held-out stream shares them)."""
    k_pick, k_bg, k_mix = jax.random.split(key, 3)
    topic_vocab = jax.random.randint(topic_key, (n_topics, topic_words),
                                     RESERVED, vocab)
    assert n * n_topics < 2 ** 31, "topic index would overflow int32"
    topic = (jnp.arange(n) * n_topics) // n
    pick = jax.random.randint(k_pick, (n, length), 0, topic_words)
    topical = topic_vocab[topic[:, None], pick]
    background = jax.random.randint(k_bg, (n, length), RESERVED, vocab)
    is_topical = jax.random.uniform(k_mix, (n, length)) < topical_share
    return jnp.where(is_topical, topical, background).astype(jnp.int32)


@jax.jit
def passage_keys(docs, table):
    """Normalised mean of each passage's token rows, one token column at a
    time so no (n, length, dim) tensor is made."""
    def body(t, acc):
        return acc + table[docs[:, t]]
    acc = jax.lax.fori_loop(0, docs.shape[1], body,
                            jnp.zeros((docs.shape[0], table.shape[1]),
                                      jnp.float32))
    acc = acc / docs.shape[1]
    return acc / jnp.maximum(jnp.linalg.norm(acc, axis=1, keepdims=True),
                             1e-9)


@partial(jax.jit, static_argnames=("n_entries", "window"))
def fir_keys(stream, table, decay: float, n_entries: int, window: int):
    """Datastore keys: entry i's context is stream[i : i + window], tap j
    (weight decay**j) on stream[i + window - 1 - j]."""
    idx = jnp.arange(n_entries)

    def body(j, acc):
        w = jnp.asarray(decay, jnp.float32) ** j.astype(jnp.float32)
        return acc + w * table[stream[idx + window - 1 - j]]
    acc = jax.lax.fori_loop(0, window, body,
                            jnp.zeros((n_entries, table.shape[1]),
                                      jnp.float32))
    return acc / jnp.maximum(jnp.linalg.norm(acc, axis=1, keepdims=True),
                             1e-9)
