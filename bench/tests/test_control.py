"""The check must fail what it exists to catch: the lower-precision
control, and each fault a serving cell can have planted under the timed
path (the rest of the run as on the chip, at a tiny size on the CPU)."""
import numpy as np
import pytest

from bench.tests.helpers import HERE, TINY, run_tiny

CELLS = ["tiny-ralm.qa", "tiny-knnlm.lm"]
# the faults are planted in the kind of cell BENCHMARK.json holds (KNN-LM);
# on the tiny RaLM configuration a stale KV cache can read 0 (Open questions)
FAULT_CELLS = ["tiny-knnlm.lm"]


def state_unchanged(sysm):
    """The decode step returns the state it was given."""
    eng = sysm.engine
    decode = eng._decode_jit
    eng._decode_jit = lambda p, st, tok, pos: (decode(p, st, tok, pos)[0], st)


def half_kb_left_out(sysm):
    """The KB call scans only the first half of the rows."""
    from repro.retrieval.backends import canonical_topk
    half = sysm.corpus.keys[:len(sysm.corpus.keys) // 2]
    sysm.retriever.backend.search = \
        lambda q, k: canonical_topk(np.asarray(q, np.float32) @ half.T, k)


def token_altered(sysm):
    """Every third engine call serves one token off by one."""
    eng, V, n = sysm.engine, sysm.cfg["vocab_size"], [0]
    if sysm.cfg["workload"] == "knnlm":
        advance = eng.advance

        def adv(slots, toks):
            n[0] += 1
            toks = list(toks)
            if n[0] % 3 == 0:
                toks[0] = (int(toks[0]) + 1) % V
            return advance(slots, toks)
        eng.advance = adv
        return
    gen = eng.gen

    def g(slots, ks):
        out = gen(slots, ks)
        n[0] += 1
        if n[0] % 3 == 0 and out[0]:
            t = (out[0][-1] + 1) % V
            eng.tokens[int(slots[0])][-1] = t
            out[0][-1] = t
        return out
    eng.gen = g


def answer_altered(sysm):
    """Every fifth KB call answers its first query with the wrong row."""
    b, N, n = sysm.retriever.backend, len(sysm.corpus.keys), [0]
    search = b.search

    def s(q, k):
        ids, sc = search(q, k)
        n[0] += 1
        if n[0] % 5 == 0:
            ids = ids.copy()
            ids[0, 0] = (ids[0, 0] + 1) % N
        return ids, sc
    b.search = s


FAULTS = [state_unchanged, half_kb_left_out, token_altered, answer_altered]


@pytest.mark.parametrize("cell", FAULT_CELLS)
@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    out = run_tiny(cell, seed=11, fault=fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails_and_program_passes(cell):
    from bench import calibrate, harness
    bm, _, cfg, _ = harness.prepare(cell, TINY, [HERE])
    rows = calibrate.calibrate(cell, [21, 22, 23], 2.0, ["bfloat16"],
                               bench_path=TINY, data_dirs=[HERE],
                               allow_cpu=True, log=lambda *a: None)
    lim = cfg["limits"]
    for r in rows:
        assert all(r["program"][n] <= lim[n] for n in lim), r
        assert any(r["bfloat16"][n] > lim[n] for n in lim), r
