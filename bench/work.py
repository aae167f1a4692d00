"""Work that a call must do, counted from shapes alone, and the chip's peaks.

Counted from what the algorithm needs, not from what an implementation
happens to move, so the numbers stay put whatever serves the call:

  * an exact dense KB call over N rows of width d for B queries, top-k:
    it must read the KB once and the queries, and write k (score, id)
    pairs per query; it must do 2·B·N·d multiply-adds' worth of FLOPs;
  * an LM forward per token: 2 FLOPs per matmul parameter (the
    unembedding included), plus attention's 4·layers·heads·head_dim FLOPs
    per position of context attended."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add it with its source")
    return table[device_kind]


def kb_call_bytes(n_rows: int, dim: int, batch: int, k: int,
                  itemsize: int = 4) -> int:
    return n_rows * dim * itemsize + batch * dim * 4 + batch * k * 8


def kb_call_flops(n_rows: int, dim: int, batch: int) -> int:
    return 2 * batch * n_rows * dim


def roofline_s(flops: float, nbytes: float, pk: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])


def lm_flops_per_token(matmul_params: int, n_layers: int, n_heads: int,
                       head_dim: int, context: float) -> float:
    return 2.0 * matmul_params + 4.0 * n_layers * n_heads * head_dim * context
