"""ssm_decode_roofline: the decode step's Mamba layers' share of their
roofline, %.  Reads the program's scopes (``chipbench/scopes.py``): in the
decode-chunk programs, the ops under ``ssm`` -- the Mamba projections, the
conv, the state update and the gated norm, and the reads and writes of
each layer's state in the stack.

What they need (``chipbench/flops.py`` and the model's kind): every loop
iteration reads the ``ssm`` scope's weights once (bf16), and reads and
writes every slot's recurrent state in every Mamba layer: the SSM state
(heads x head size x state) and the conv window ((ssm_conv - 1) x
(d_inner + the B and C columns)).  The state is counted at 2 bytes an
element, the configuration's bf16, so the share stays a lower bound
whatever precision the program holds it in (its float32 SSM state caps
the share near 82% at 24 slots of zamba2-7b).  The decode loop runs every
slot, so the state is counted for every slot.  The least time is
iterations x those bytes / HBM bandwidth; the share is that over the ssm
ops' device time in the traced window.  Iterations are counted as
``decode_iter_ms`` counts them."""

import sys

from chipbench import flops, reference, scopes

MODULE = "jit_decode_chunk"
SCOPE = "ssm"


def state_elements(m: dict) -> int:
    """One slot's recurrent state in one Mamba layer, in elements."""
    d_inner = m["ssm_expand"] * m["d_model"]
    conv = (m["ssm_conv"] - 1) * (d_inner + reference.kind(m).bc_width(m))
    return flops.ssm_heads(m) * m["ssm_headdim"] * m["ssm_state"] + conv


def read(rec):
    if not isinstance(rec.trace, scopes.Scoped):
        return None
    ssm_s = rec.trace.scope_s(MODULE).get(SCOPE, 0.0)
    m = rec.model
    layers = flops.ssm_layers(m)
    if ssm_s <= 0 or not layers:
        return None
    steps = rec.window_steps(traced=True)
    iters = sum(max((n for _, n in s.tokens.values()), default=0)
                for s in steps)
    if not iters:
        return None
    w_bytes = flops.BF16 * flops.ssm_scope_weights(m)
    s_bytes = (rec.serve["batch"] * layers * 2 * flops.BF16
               * state_elements(m))
    t_b = iters * (w_bytes + s_bytes) / rec.peaks["hbm_bytes_per_s"]
    print(f"[metric] decode ssm: {iters} iterations; per iteration "
          f"{w_bytes} weight bytes, {s_bytes} state bytes (read and "
          f"written, 2 a element); ssm ops {ssm_s:.6g} s", file=sys.stderr)
    return 100.0 * t_b / ssm_s
