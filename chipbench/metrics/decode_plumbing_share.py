"""decode_plumbing_share: share of the decode-chunk programs' op time in
ops that belong to no part of the model, %: their scope path
(``chipbench/scopes.py``) holds none of the model scopes, or they have
none -- the layer scan's per-layer weight slices, layout copies and the
loop's bookkeeping.  Prints the seconds of every scope to stderr.  Nothing
to read in a program without scopes (every op would count)."""

import sys

from chipbench import scopes

MODULE = "jit_decode_chunk"
OUTSIDE = (scopes.PLUMBING, scopes.UNSCOPED)


def read(rec):
    if not isinstance(rec.trace, scopes.Scoped):
        return None
    by = rec.trace.scope_s(MODULE)
    total = sum(by.values())
    if total <= 0 or not set(by) - set(OUTSIDE):
        return None
    print("[metric] decode scopes, s: " + ", ".join(
        f"{k} {v:.6g}" for k, v in sorted(by.items(), key=lambda kv: -kv[1])),
        file=sys.stderr)
    return 100.0 * sum(by.get(k, 0.0) for k in OUTSIDE) / total
