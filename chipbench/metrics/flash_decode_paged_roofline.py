"""flash_decode_paged_roofline: the paged decode-attention kernel's share
of its roofline inside the decode-chunk programs, %.

What the algorithm needs (chipbench/flops.py): for every decoded token,
the K and V rows up to its slot's length, the query and the output, in
every attention layer; and 4 x rows x heads x head size FLOPs.  The least
time is the larger of bytes / HBM bandwidth and FLOPs / peak; the share is
that over the kernel's device time in the traced window."""

import sys

from chipbench import flops
from chipbench.metrics import decode_tokens

KERNEL = "flash_decode_paged"
MODULE = "jit_decode_chunk"


def read(rec):
    if rec.trace is None:
        return None
    dev_s = rec.trace.op_s(KERNEL, module=MODULE)
    if dev_s <= 0:
        return None
    m = rec.model
    need_b = need_f = 0.0
    for plen, k in decode_tokens(rec, traced=True):
        need_b += flops.decode_attention_bytes(m, plen + k)
        need_f += flops.attention_flops(m, plen + k)
    t_b = need_b / rec.peaks["hbm_bytes_per_s"]
    t_f = need_f / rec.peaks["bf16_flops"]
    print(f"[metric] flash_decode_paged: {need_b:.6g} bytes, {need_f:.6g} "
          f"FLOPs, kernel {dev_s:.6g} s; bound by "
          f"{'memory' if t_b >= t_f else 'compute'}", file=sys.stderr)
    return 100.0 * max(t_b, t_f) / dev_s
