"""Share of its roofline that the decode step reaches.

The least time of a step is the larger of its operations over the chip's
peak and its least bytes over the chip's bandwidth (``flops.py``: every
weight once, one embedding row per request, the K/V of each request's live
tokens and the new token's K/V; no padding, pool copy or gathered copy).
The share is the mean least time of the window's decode steps over the
mean device time of the decode program's events in the trace."""
from chipbench import flops
from chipbench.peaks import peaks_for


def read(data):
    if data.trace is None:
        return None
    durs = data.trace.module_durations("decode")
    calls = data.decode_calls()
    if not durs or not calls:
        return None
    pk = peaks_for(data.device["kind"])
    cfg = data.cell.config
    least = [max(flops.decode_step_flops(cfg, ctx) / pk["bf16_flops"],
                 flops.decode_step_bytes(cfg, ctx) / pk["hbm_bytes_per_s"])
             for _, ctx in calls]
    return 100.0 * (sum(least) / len(least)) / (sum(durs) / len(durs) / 1e9)
