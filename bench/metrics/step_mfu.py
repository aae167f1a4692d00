"""Whole serving step's share of the chip's peak: output tokens per second
times the LM forward FLOPs each served token needs (2 per matmul parameter,
the unembedding included, plus attention over the context it attends), over
the peak for the device kind (``peaks.json``)."""
from bench import stack, work


def read(run):
    if not run.tokens:
        return None
    cfg, mc = run.cfg, run.model_cfg
    ref = stack.reference(cfg)
    chunk = cfg["serving"]["chunk_len"] if cfg["workload"] == "ralm" else 0
    ctx = sum(sum(chunk + len(p) + i for i in range(len(t)))
              for p, t in run.served) / run.tokens
    flops = work.lm_flops_per_token(ref.matmul_params(mc), mc["num_layers"],
                                    mc["num_heads"], mc["head_dim"], ctx)
    return 100.0 * (run.tokens / run.window_s) * flops / \
        work.peaks(run.device_kind)["flops_bf16"]
