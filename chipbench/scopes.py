"""The program's own names in a profiler trace: the model scope of every
device op and the serving engine's ``serve.*`` host spans.

``reduce(path)`` returns a ``Scoped``: everything ``chipbench.trace.reduce``
returns, unchanged, and two more fields --

  * ``scopes``, parallel to ``ops``: each op's ``tf_op`` path (the JAX name
    stack it was staged under, ``chipbench/xplane.py``), "" where the
    trace records none;
  * ``serve``: the engine's host spans (``serve.step``, ``.admit``, ...),
    each (name, start ns, duration ns, args).

``model_scope`` names the part of the model an op belongs to: one of
``MODEL_SCOPES`` (``jax.named_scope`` in ``models/`` and the decode loop),
``plumbing`` where its path holds none of them (the layer scan's weight
slices, layout copies, loop bookkeeping), ``unscoped`` where it has no
path.  A program without scopes or engine spans reduces to a ``Scoped``
whose ops are all plumbing and whose ``serve`` is empty; the readers built
on it then find nothing to read.

``python3 chipbench/scopes.py <file.xplane.pb> [<program>]`` prints the
device seconds per scope of each program (or of one), and the device idle
time inside ``serve.step`` spans split by the innermost ``serve.*`` span.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace as T  # noqa: E402
from chipbench import xplane  # noqa: E402

MODEL_SCOPES = ("embed", "norm", "attn_proj", "attn_core", "mlp", "ssm",
                "logits", "sample")
PLUMBING = "plumbing"
UNSCOPED = "unscoped"
SERVE_PREFIX = "serve."
STEP_SPAN = "serve.step"
NO_SPAN = "none"


def model_scope(path: str) -> str:
    """The innermost of ``MODEL_SCOPES`` among the path's components after
    the first, which names the program (``jit(decode_chunk)``) or, for an
    argument's copy, the argument (``logits:``)."""
    if not path:
        return UNSCOPED
    for part in reversed(path.split(":", 1)[0].split("/")[1:]):
        if part in MODEL_SCOPES:
            return part
    return PLUMBING


@dataclasses.dataclass
class Scoped(T.Reduced):
    scopes: list = dataclasses.field(default_factory=list)
    serve: list = dataclasses.field(default_factory=list)

    def scope_s(self, module: str) -> dict[str, float]:
        """Device seconds of the ops of the programs named ``module`` by
        ``model_scope``, averaged over the chips."""
        n = max(self.n_devices, 1)
        out = collections.Counter()
        for op, path in zip(self.ops, self.scopes):
            if op.module == module:
                out[model_scope(path)] += op.dur * 1e-9 / n
        return dict(out)

    def serve_spans(self, name: str) -> list[tuple[int, int]]:
        """(start, end) ns of the ``name`` spans inside the window."""
        lo, hi = self.window
        return [(s, s + d) for n, s, d, _ in self.serve
                if n == name and s >= lo and s + d <= hi]

    def step_idle(self) -> tuple[list[int], dict[str, int]]:
        """Device-idle ns inside each ``serve.step`` span, and the idle ns
        of all steps by the innermost ``serve.*`` span that covers them
        (``none``: inside the step, in no inner span)."""
        return self.idle_split(self.serve_spans(STEP_SPAN), skip=STEP_SPAN)

    def idle_split(self, outer, skip: str | None = None):
        """Device-idle ns on the first chip inside each (start, end) ns
        interval of ``outer``, and their idle ns by the innermost
        ``serve.*`` span (but ``skip``) that covers them."""
        devs = sorted({o.device for o in self.ops})
        busy = T.merge([(o.start, o.start + o.dur) for o in self.ops
                        if not devs or o.device == devs[0]])
        inner = sorted((s, s + d, d, n) for n, s, d, _ in self.serve
                       if n != skip)
        per, split = [], collections.Counter()
        for lo, hi in outer:
            idle = gaps(busy, lo, hi)
            per.append(sum(e - s for s, e in idle))
            spans = [sp for sp in inner if sp[0] < hi and sp[1] > lo]
            for s, e in idle:
                for a, b, name in innermost(spans, s, e):
                    split[name] += b - a
        return per, dict(split)


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """Stretches of [lo, hi) that no merged busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(spans, s: int, e: int):
    """[s, e) cut at the edges of ``spans`` ((start, end, duration, name)
    each), every piece named by the shortest span covering it (spans nest,
    so that is the innermost) or ``none``."""
    cuts = sorted({s, e} | {x for sp in spans for x in sp[:2] if s < x < e})
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in spans if sp[0] <= a and sp[1] >= b]
        name = min(cover, key=lambda sp: sp[2])[3] if cover else NO_SPAN
        yield a, b, name


def reduce(path: str) -> Scoped:
    from jax.profiler import ProfileData

    base = T.reduce(path)
    paths = xplane.scopes(path)
    pd = ProfileData.from_file(path)
    raw, serve = [], []
    for plane in pd.planes:
        dev = T._device_index(plane.name)
        for line in plane.lines:
            if dev is None:
                serve += [(ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)) for ev in line.events
                          if ev.name.startswith(SERVE_PREFIX)]
            elif line.name == "XLA Ops":
                raw += [(int(ev.start_ns), int(ev.duration_ns), ev.name,
                         plane.name) for ev in line.events
                        if T.base_name(ev.name) not in T.CONTAINERS]
    # the ops trace.reduce keeps, in its order: sorted by start (stably),
    # those that overlap the window
    lo, hi = base.window
    raw = [r for r in sorted(raw, key=lambda r: r[0])
           if r[0] < hi and r[0] + r[1] > lo]
    if len(raw) != len(base.ops):
        raise ValueError(f"{path}: {len(raw)} ops against the reduction's "
                         f"{len(base.ops)}")
    scopes = []
    for (_, _, name, plane), op in zip(raw, base.ops):
        if T.base_name(name) != op.name:
            raise ValueError(f"{path}: op {name!r} against {op.name!r}")
        scopes.append(xplane.scope_for(paths.get(plane, {}).get(name, []),
                                       op.module))
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(T.Reduced)}
    return Scoped(**fields, scopes=scopes, serve=serve)


def summary(r: Scoped, module: str | None = None) -> str:
    out = []
    mods = sorted({o.module for o in r.ops} if module is None else {module})
    for m in mods:
        by = r.scope_s(m)
        tot = sum(by.values())
        out.append(f"{m or '(no program)'}: {tot:.6f} s in ops")
        for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
            out.append(f"  {name:10s} {s:.6f} s  {100 * s / tot:6.2f}%")
    per_step, split = r.step_idle()
    idle = sum(per_step)
    out.append(f"serve.step spans: {len(per_step)}, device idle inside "
               f"them {idle * 1e-9:.6f} s")
    for name, ns in sorted(split.items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:14s} {ns * 1e-9:.6f} s  "
                   f"{100 * ns / max(idle, 1):6.2f}%")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(reduce(sys.argv[1]),
                  sys.argv[2] if len(sys.argv) > 2 else None))
