"""engine_idle_ms: device-idle milliseconds per engine step -- inside each
of the engine's ``serve.step`` spans in the traced window, the time with no
device op, averaged over the steps.  Prints the idle time split by the
innermost ``serve.*`` span around it (admit, prefill, decode, fetch,
deliver; ``none``: in the step, in no inner span) to stderr.  Nothing to
read in a program without engine spans."""

import sys

from chipbench import scopes


def read(rec):
    if not isinstance(rec.trace, scopes.Scoped):
        return None
    per_step, split = rec.trace.step_idle()
    if not per_step:
        return None
    idle = sum(per_step)
    print("[metric] engine idle by span, ms: " + ", ".join(
        f"{k} {v * 1e-6:.6g}" for k, v in sorted(split.items(),
                                                 key=lambda kv: -kv[1]))
        + f"; {len(per_step)} steps, {idle * 1e-6:.6g} ms",
        file=sys.stderr)
    return idle * 1e-6 / len(per_step)
