"""prefill_device_share: device time of the prefill-chunk programs over
the traced window, %.  Moves ttft_p95_ms: prefill is what a new request
waits for."""

MODULE = "jit_prefill_chunk"


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    dev_s = rec.trace.module_s(MODULE)
    if dev_s <= 0:
        return None
    return 100.0 * dev_s / rec.trace.window_s
