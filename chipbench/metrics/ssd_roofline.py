"""ssd_roofline: the Mamba-2 SSD kernel's share of its roofline in the
prefill-chunk programs, %.

Every prefill chunk of two rows or more runs the kernel in every Mamba
layer (a one-row chunk takes the decode recurrence instead).  Operations
and bytes per call (chipbench/flops.py, after hw/flops._ssd_flops_fwd):
2 (QN + QP + 2PN) FLOPs per row and head at the program's chunk Q; x, dt,
B, C in and y out in bf16, the carried and final state in f32.  The least
time is the larger of FLOPs / peak and bytes / HBM bandwidth; the share is
that over the kernel's device time in the traced window."""

import sys

from chipbench import flops
from chipbench.metrics import admitted

KERNEL = "ssd"
MODULE = "jit_prefill_chunk"


def read(rec):
    if rec.trace is None:
        return None
    dev_s = rec.trace.op_s(KERNEL, module=MODULE)
    if dev_s <= 0:
        return None
    m = rec.model
    need_f = need_b = 0.0
    for _, plen in admitted(rec, traced=True):
        for rows in flops.chunk_plan(plen, rec.serve["prefill_chunk"]):
            if rows > 1:
                need_f += flops.ssd_flops(m, rows)
                need_b += flops.ssd_bytes(m, rows)
    if need_f <= 0:
        return None
    t_f = need_f / rec.peaks["bf16_flops"]
    t_b = need_b / rec.peaks["hbm_bytes_per_s"]
    print(f"[metric] ssd: {need_f:.6g} FLOPs, {need_b:.6g} bytes, kernel "
          f"{dev_s:.6g} s; bound by "
          f"{'compute' if t_f >= t_b else 'memory'}", file=sys.stderr)
    return 100.0 * max(t_f, t_b) / dev_s
