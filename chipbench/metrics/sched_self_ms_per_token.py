"""Host self time of the scheduler (``step``, ``_admit``, ``_resume``,
``_step_active``) in the window, less the orchestration and model-step
spans nested in it, per output token of the window."""
from chipbench.spans import layer_time_ns


def read(data):
    spans = data.served.rec.spans
    if not spans or not data.tokens:
        return None
    t0, t1 = data.window
    return layer_time_ns(spans, "scheduler", t0, t1) / 1e6 / data.tokens
