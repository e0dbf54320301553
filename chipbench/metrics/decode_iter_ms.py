"""decode_iter_ms: device milliseconds per decode iteration -- the device
time of the decode-chunk programs in the traced window over the loop
iterations they ran (per chunk, the most tokens any slot got)."""

MODULE = "jit_decode_chunk"


def read(rec):
    if rec.trace is None:
        return None
    steps = rec.window_steps(traced=True)
    iters = sum(max((n for _, n in s.tokens.values()), default=0)
                for s in steps)
    dev_s = rec.trace.module_s(MODULE)
    if not iters or dev_s <= 0:
        return None
    return 1e3 * dev_s / iters
