"""Model operations of the traced window over its seconds times the chip's
peak: every prompt token prefilled (causal attention at its real context)
and every token decoded (attention over its live context), matmuls without
the embedding lookup (``flops.py``)."""
from chipbench import flops
from chipbench.peaks import peaks_for


def read(data):
    if data.trace is None or not data.tokens:
        return None
    cfg = data.cell.config
    total = sum(flops.prefill_flops(cfg, n) for n in data.prefills())
    total += sum(flops.decode_step_flops(cfg, ctx)
                 for _, ctx in data.decode_calls())
    pk = peaks_for(data.device["kind"])
    return 100.0 * total / (data.served.window_s * pk["bf16_flops"])
