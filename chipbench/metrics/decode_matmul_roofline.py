"""decode_matmul_roofline: the decode step's weight matmuls' share of their
roofline, %.  Reads the program's scopes (``chipbench/scopes.py``): in the
decode-chunk programs, the ops under ``attn_proj``, ``mlp`` and ``logits``,
and the ops under no model scope, which move those weights into fast
memory for them (the layer scan's weight slices, a hybrid's prefetched
shared block).  The matmuls' own time leaves that part of the weight
traffic out: over it alone the share read 101.1% on one v5e.

What they need (chipbench/flops.py): every loop iteration reads those
scopes' weights once (bf16, the unembedding included), and every decoded
token multiplies through them, 2 FLOPs a weight.  The least time is the
larger of iterations x bytes / HBM bandwidth and tokens x FLOPs / peak;
the share is that over those ops' device time in the traced window.
Iterations are counted as ``decode_iter_ms`` counts them."""

import sys

from chipbench import flops, scopes

MODULE = "jit_decode_chunk"
SCOPES = ("attn_proj", "mlp", "logits")
FEED = (scopes.PLUMBING, scopes.UNSCOPED)


def weights(m: dict) -> int:
    """Weights of the attn_proj, mlp and logits scopes: every matmul weight
    but those the model's kind runs in its ssm scope, and the
    unembedding."""
    return (flops.matmul_weights_per_token(m) + m["d_model"] * m["vocab"]
            - flops.ssm_scope_weights(m))


def read(rec):
    if not isinstance(rec.trace, scopes.Scoped):
        return None
    by = rec.trace.scope_s(MODULE)
    mm_s = sum(by.get(s, 0.0) for s in SCOPES)
    if mm_s <= 0:
        return None
    feed_s = sum(by.get(s, 0.0) for s in FEED)
    dev_s = mm_s + feed_s
    steps = rec.window_steps(traced=True)
    iters = sum(max((n for _, n in s.tokens.values()), default=0)
                for s in steps)
    tokens = sum(n for s in steps for _, n in s.tokens.values())
    w = weights(rec.model)
    t_b = iters * flops.BF16 * w / rec.peaks["hbm_bytes_per_s"]
    t_f = tokens * 2.0 * w / rec.peaks["bf16_flops"]
    print(f"[metric] decode matmuls: {iters} iterations, {tokens} tokens, "
          f"{w} weights; matmuls {mm_s:.6g} s, ops under no scope "
          f"{feed_s:.6g} s; bound by "
          f"{'memory' if t_b >= t_f else 'compute'}", file=sys.stderr)
    return 100.0 * max(t_b, t_f) / dev_s
