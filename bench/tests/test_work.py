"""Work functions and the peak table."""
import pytest

from bench import work
from bench.references import dense_decoder


def test_kb_call_work_from_shapes():
    assert work.kb_call_bytes(1000, 768, 4, 20) == 1000 * 768 * 4 + 4 * 768 * 4 + 4 * 20 * 8
    assert work.kb_call_flops(1000, 768, 4) == 2 * 4 * 1000 * 768
    pk = work.peaks("TPU v5 lite")
    # the full 1,313,458 x 768 scan is bound by HBM at any batch a cell makes
    need = work.roofline_s(work.kb_call_flops(1313458, 768, 136),
                           work.kb_call_bytes(1313458, 768, 136, 20), pk)
    assert need == pytest.approx(work.kb_call_bytes(1313458, 768, 136, 20)
                                 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.peaks("source")


def test_lm_flops_count_matmul_parameters():
    cfg = dict(num_layers=24, d_model=1024, num_heads=16, num_kv_heads=16,
               head_dim=64, d_ff=4096, vocab_size=50257)
    p = dense_decoder.matmul_params(cfg)
    assert p == 24 * (4 * 1024 * 1024 + 3 * 1024 * 4096) + 1024 * 50257
    assert work.lm_flops_per_token(p, 24, 16, 64, 0) == 2 * p
