"""mfu: model FLOPs of every token prefilled and decoded in the traced
window's steps over (traced window seconds x the chip's peak bf16 FLOP/s),
%.

Counts (chipbench/flops.py): 2 x the weights a token multiplies through,
causal attention over the rows it sees, the SSD scan, and logits -- one
row per decoded token and one per admitted prompt.  Work the program
wastes (done slots in the decode loop, re-prefilled prefixes) is not
counted.  The window is the harness's ``chipbench.window`` span in the
trace, which holds every traced step whole and ends before the profiler
stops, so the share is the whole step's: device idle time and host work
between steps count against it, the profiler's own dump does not."""

from chipbench import flops
from chipbench.metrics import admitted, decode_tokens


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    m = rec.model
    total = 0.0
    for plen, k in decode_tokens(rec, traced=True):
        total += flops.decode_flops(m, plen + k)
    chunk = rec.serve["prefill_chunk"]
    for _, plen in admitted(rec, traced=True):
        off = 0
        for rows in flops.chunk_plan(plen, chunk):
            total += flops.prefill_flops(m, off, rows)
            off += rows
        total += flops.logits_flops(m, 1)
    if total <= 0:
        return None
    return 100.0 * total / (rec.trace.window_s * rec.peaks["bf16_flops"])
