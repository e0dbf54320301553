"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the per-layer metrics read.

``reduce(path)`` returns a ``Reduced``:

  * the traced window: the harness's own ``chipbench.window`` host span;
  * every device operation inside it (the ``XLA Ops`` line of each
    ``/device:TPU:n`` plane), by its HLO name without the ".N" suffix --
    a Pallas kernel carries the name of the jitted function around its
    pallas_call (``flash_decode_paged``, ``ssd``) -- with the program
    (``XLA Modules`` line, e.g. ``jit_decode_chunk``) it ran in; loops
    and calls, which only contain other ops, are left out;
  * busy seconds: the union of the operations' intervals, per chip;
  * idle gaps: the stretches of the window with no operation running,
    each attributed to the harness span (``chipbench.*``) that covers
    most of it -- what the host was doing meanwhile.

Run as a script, ``python3 chipbench/trace.py <file.xplane.pb>`` prints a
summary of the planes, lines and most frequent event names: how to look
at a trace by hand before trusting the reduction.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
_SUFFIX = re.compile(r"\(\d+\)$")
# ops that only contain others (a while loop spans its body's ops): left
# out, so that busy time and the top ops count each op once
CONTAINERS = {"while", "conditional", "call"}


def base_name(hlo_text: str) -> str:
    """An XLA Ops event is named by its HLO instruction text
    ("%flash_decode_paged.6 = bf16[...] custom-call(...)"): the name with
    its ".N" suffix dropped ("flash_decode_paged").  A Pallas kernel is
    named after the jitted function that wraps its pallas_call."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%").strip()
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclasses.dataclass
class Op:
    name: str           # base name (``base_name``)
    module: str         # program the op ran in, "(NNN)" suffix dropped
    start: int          # ns
    dur: int            # ns
    device: int


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]
    ops: list[Op]
    modules: list[tuple[str, int, int, int]]    # (name, start, dur, device)
    host: list[tuple[str, int, int]]            # harness spans
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        devs = sorted({o.device for o in self.ops})
        if not devs:
            return 0.0
        return sum(union_ns([(o.start, o.start + o.dur) for o in self.ops
                             if o.device == d]) for d in devs) \
            * 1e-9 / len(devs)

    def module_s(self, module: str) -> float:
        """Device seconds of the programs named ``module`` (the jitted
        function's name, e.g. "jit_decode_chunk"), averaged over the
        chips."""
        n = max(self.n_devices, 1)
        return sum(d for name, _, d, _ in self.modules
                   if name == module) * 1e-9 / n

    def op_s(self, name: str, module: str | None = None) -> float:
        """Device seconds of the ops named ``name`` (inside the programs
        named ``module``), averaged over the chips."""
        n = max(self.n_devices, 1)
        return sum(o.dur for o in self.ops if o.name == name
                   and (module is None or o.module == module)) * 1e-9 / n

    def top_ops(self, k: int = 10) -> list[list]:
        tot = collections.Counter()
        for o in self.ops:
            tot[o.name] += o.dur
        n = max(self.n_devices, 1)
        return [[name, ns * 1e-9 / n] for name, ns in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The k longest stretches with no device op on the first chip,
        named by the harness span that covers most of each."""
        devs = sorted({o.device for o in self.ops})
        busy = merge([(o.start, o.start + o.dur) for o in self.ops
                      if not devs or o.device == devs[0]])
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in gaps:
            best, cover = "no harness span", 0
            for name, hs, hd in self.host:
                c = min(e, hs + hd) - max(s, hs)
                if c > cover and name != WINDOW_SPAN:
                    best, cover = name, c
            out.append([best, (e - s) * 1e-9])
        return out


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> int:
    return sum(e - s for s, e in merge(intervals))


def _device_index(plane_name: str) -> int | None:
    m = re.match(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, modules, raw_ops = [], [], []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is None:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    modules.append((_SUFFIX.sub("", ev.name),
                                    int(ev.start_ns), int(ev.duration_ns),
                                    dev))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    name = base_name(ev.name)
                    if name not in CONTAINERS:
                        raw_ops.append((name, int(ev.start_ns),
                                        int(ev.duration_ns), dev))
    wins = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if wins:
        window = (min(w[0] for w in wins), max(w[1] for w in wins))
    elif raw_ops:
        window = (min(o[1] for o in raw_ops),
                  max(o[1] + o[2] for o in raw_ops))
    else:
        window = (0, 0)
    lo, hi = window
    modules = [m for m in modules if m[1] < hi and m[1] + m[2] > lo]
    by_dev = collections.defaultdict(list)
    for m in sorted(modules, key=lambda m: m[1]):
        by_dev[m[3]].append(m)
    ops = []
    for name, s, d, dev in sorted(raw_ops, key=lambda o: o[1]):
        if not (s < hi and s + d > lo):
            continue
        s2, e2 = max(s, lo), min(s + d, hi)
        ops.append(Op(name=name, module=_module_at(by_dev[dev], s),
                      start=s2, dur=e2 - s2, device=dev))
    modules = [(n, max(s, lo), min(s + d, hi) - max(s, lo), dev)
               for n, s, d, dev in modules]
    devs = {o.device for o in ops} | {m[3] for m in modules}
    return Reduced(window=window, ops=ops, modules=modules, host=host,
                   n_devices=len(devs))


def _module_at(mods, t: int) -> str:
    """The program running at time t (mods sorted by start)."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][1] + mods[lo - 1][2] >= t:
        return mods[lo - 1][0]
    return ""


def summarize(path: str, top: int = 40) -> str:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(top):
                out.append(f"    {n:7d} x {name[:160]}")
            for e in evs[:3]:
                out.append(f"    sample {e.name[:80]!r} start {e.start_ns} "
                           f"dur {e.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summarize(sys.argv[1]))
