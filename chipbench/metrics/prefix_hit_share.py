"""prefix_hit_share: prompt rows the engine mapped from cached prefixes
instead of prefilling (its prefill_tokens_skipped counter, over the
window) over the prompt rows of the requests admitted in the window, %.
Moves ttft_p95_ms.  Nothing to read where prefix sharing is off or no
request declares a shareable prefix."""

from chipbench.metrics import admitted


def read(rec):
    if not rec.serve.get("prefix_sharing"):
        return None
    if not any(r.spec.prefix_len for r in rec.reqs.values()
               if r.attempted or r.first is not None):
        return None
    rows = sum(plen for _, plen in admitted(rec, traced=False))
    c0, c1 = rec.counters.get("start"), rec.counters.get("end")
    if not rows or c0 is None or c1 is None:
        return None
    skipped = c1["prefill_tokens_skipped"] - c0["prefill_tokens_skipped"]
    return 100.0 * skipped / rows
