"""Per-layer metric readers, one file each, found by the metric's name.

``read(name, rec)`` loads ``chipbench/metrics/<name>.py`` -- or, for a
metric split by the end-to-end metric it moves (``decode_iter_ms.batch``,
``mfu.chat``), the reader of the name before the first dot -- and calls
its ``read(rec)``.  ``rec`` is the run's ``harness.Record``: its steps,
requests, engine counters and, in a ``--trace 1`` run, the reduced trace.
A reader that finds nothing to read returns None, and the metric is left
out of the result line; it never stands in a 0 for a share it could not
measure.
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def reader_path(name: str) -> str:
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, f"{stem}.py")
        if os.path.exists(path):
            return path
    raise KeyError(f"no reader for metric {name!r} in {HERE}")


def read(name: str, rec):
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{os.path.basename(path)[:-3]}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def decode_tokens(rec, traced: bool):
    """(prompt length, token index) of every token delivered by a decode
    iteration in the window's steps: the iteration that delivers token k
    (1-based) of a request runs the model on it at position prompt + k - 1,
    so its query sees prompt + k rows."""
    for st in rec.window_steps(traced):
        for uid, (before, n) in st.tokens.items():
            plen = rec.prompt_len(uid)
            for k in range(before + 1, before + n + 1):
                yield plen, k


def admitted(rec, traced: bool):
    """(uid, prompt length) of the requests admitted in the window's
    steps (their prefill ran in the step that delivered their first
    token)."""
    for st in rec.window_steps(traced):
        for uid in st.admitted:
            yield uid, rec.prompt_len(uid)
