"""One run of one serving cell: set-up, the measured window, the check.

The program is driven only through its public entry: ``ServeEngine(...,
paged=True)``, then ``submit()`` and ``step()``; tokens are read off the
requests it fills.  Everything else here is the benchmark's: weights drawn
from the seed, traffic from the cell's data file, timing on the host
clock, the trace reduction, and the comparison with the plain reference.

Phases, each timed on the host clock:

  set-up   weights drawn on the device; the engine built at the cell's
           fixed pool size; every program the traffic can run warmed once
           (one request whose prompt splits into every prefill chunk size,
           256 down to 1); a lead-in of the cell's own traffic until the
           slots are in steady state.  ``setup_s`` ends at the window.
  window   ``seconds`` of traffic.  Closed loop: requests are kept queued
           ahead of the engine.  Open loop: requests are submitted when
           due, each timed from when it was due.
  drain    open loop only: arrivals stop, requests due in the window
           finish, up to the mix's ``drain_limit_s``; those that do not
           are failed.
  check    peak device memory is read, the engine is freed, and a sample
           of the finished requests is run through the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import heapq
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import chipbench
from chipbench import metrics, traffic
from chipbench import reference as R

BENCHMARK = os.path.join(chipbench.ROOT, "BENCHMARK.json")
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
# the traced part of a --trace 1 window: long enough for many decode
# chunks and whole bursts, short enough that the trace stays small
TRACE_SECONDS = 10.0
# the comparison covers the longest finished request and others, to at
# least CHECK_TOKENS served tokens from at least CHECK_MIN requests, at
# most CHECK_REQUESTS
CHECK_TOKENS = 384
CHECK_MIN = 3
CHECK_REQUESTS = 6
# a closed backlog's lead-in gives up after this long (a safety net; the
# lead-in ends on completions)
LEAD_IN_LIMIT_S = 240.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell and its files
# ---------------------------------------------------------------------------

def load_cell(name: str, path: str = BENCHMARK) -> dict:
    """The cell's workload entry, configuration file, traffic parameters
    and the metrics it reports, all found by name in BENCHMARK.json."""
    with open(path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {path}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(chipbench.ROOT, conf_entry["file"])) as f:
        conf = json.load(f)

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "conf": conf,
        "traffic": traffic.load(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "run_seconds": bench["run_seconds"],
    }


def load_peaks(kind: str, path: str = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in {path}")
    return table[kind]


class CompileClock:
    """Backend compiles (persistent-cache loads included) and traces, from
    JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.compiles += 1
        elif event == self.TRACE:
            self.traces += 1


# ---------------------------------------------------------------------------
# the record a run keeps (the per-layer readers read it)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    spec: traffic.Spec
    request: object              # the program's Request
    due: float                   # host clock, s
    attempted: bool = False      # due inside the window (open loop)
    first: float | None = None   # host clock when its first token arrived
    last: float | None = None
    done: float | None = None
    n: int = 0                   # tokens delivered so far


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    phase: str                   # warm | lead | window | drain
    admitted: list = dataclasses.field(default_factory=list)
    # uid -> (tokens before this step, tokens delivered in it)
    tokens: dict = dataclasses.field(default_factory=dict)
    traced: bool = False
    queue: int = 0               # requests queued, not admitted, after it


@dataclasses.dataclass
class Record:
    name: str
    conf: dict
    model: dict                  # reference.model_view of the file
    serve: dict
    peaks: dict
    seed: int
    seconds: float
    reqs: dict = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)
    lateness: list = dataclasses.field(default_factory=list)
    window0: float = 0.0
    window1: float = 0.0
    drain_end: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    compile_s: float = 0.0
    trace: object = None         # chipbench.scopes.Scoped, --trace 1 only

    def window_steps(self, traced: bool = False) -> list:
        return [s for s in self.steps if s.phase == "window"
                and (s.traced or not traced)]

    @property
    def window_s(self) -> float:
        return self.window1 - self.window0

    def prompt_len(self, uid: int) -> int:
        return len(self.reqs[uid].spec.prompt)


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------

class Driver:
    """Submits, steps and books tokens; every call is wrapped in a host
    span so the trace can say what the host did in each device gap."""

    def __init__(self, engine, rec: Record, Request, annotate):
        self.engine, self.rec, self.Request = engine, rec, Request
        self.annotate = annotate
        self.inflight: dict[int, Req] = {}
        self.phase = "warm"
        self.traced = False

    def submit(self, spec: traffic.Spec, due: float,
               attempted: bool = False) -> None:
        now = time.perf_counter()
        req = self.Request(uid=spec.uid, prompt=list(spec.prompt),
                           max_new_tokens=spec.max_new_tokens,
                           prefix_len=spec.prefix_len)
        with self.annotate("chipbench.submit"):
            self.engine.submit([req])
        r = Req(spec=spec, request=req, due=due, attempted=attempted)
        self.rec.reqs[spec.uid] = r
        self.inflight[spec.uid] = r
        if attempted:
            self.rec.lateness.append(now - due)

    def step(self) -> list[Req]:
        """One engine step; returns the requests it finished."""
        st = Step(t0=time.perf_counter(), t1=0.0, phase=self.phase,
                  traced=self.traced)
        with self.annotate("chipbench.step"):
            finished = self.engine.step()
        st.t1 = t = time.perf_counter()
        st.queue = self.engine.queue_depth
        done = []
        with self.annotate("chipbench.deliver"):
            for uid, r in self.inflight.items():
                n = len(r.request.generated)
                if n > r.n:
                    st.tokens[uid] = (r.n, n - r.n)
                    if r.first is None:
                        r.first = t
                        st.admitted.append(uid)
                    r.last, r.n = t, n
            for req in finished:
                r = self.inflight.pop(req.uid)
                r.done = t
                done.append(r)
        self.rec.steps.append(st)
        return done


def warm_up(drv: Driver, prefill_chunk: int, decode_chunk: int, vocab: int,
            uid0: int, shares_prefixes: bool) -> None:
    """Compile (or load from the persistent cache) every program the
    traffic can run, once: a prompt of 2 * prefill_chunk - 1 tokens splits
    into every chunk size from prefill_chunk down to 1; its answer runs
    the decode chunk.  Traffic that shares prefixes also maps a cached
    prefix and copies its partial tail block."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, 2 * prefill_chunk - 1).tolist()
    specs = [traffic.Spec(uid=uid0, prompt=prompt,
                          max_new_tokens=decode_chunk + 1,
                          prefix_len=prefill_chunk + 3 if shares_prefixes
                          else 0)]
    if shares_prefixes:
        specs.append(traffic.Spec(
            uid=uid0 + 1, prompt=prompt[:prefill_chunk + 3]
            + rng.integers(0, vocab, prefill_chunk - 3).tolist(),
            max_new_tokens=decode_chunk + 1, prefix_len=prefill_chunk + 3))
    for spec in specs:
        drv.submit(spec, due=time.perf_counter())
        while drv.engine.pending:
            drv.step()


def drive_closed(drv: Driver, gen, seconds: float, tracer) -> None:
    rec, eng = drv.rec, drv.engine

    def top_up():
        while eng.queue_depth < gen.queued:
            drv.submit(gen.next(), due=time.perf_counter())

    drv.phase = "lead"
    done, t_lead = 0, time.perf_counter()
    while done < gen.lead_in_completions:
        top_up()
        done += len(drv.step())
        if time.perf_counter() - t_lead > LEAD_IN_LIMIT_S:
            raise RuntimeError(f"lead-in: {done} of "
                               f"{gen.lead_in_completions} completions in "
                               f"{LEAD_IN_LIMIT_S} s")
    drv.phase = "window"
    rec.window0 = time.perf_counter()
    tracer.start(rec.window0)
    end = rec.window0 + seconds
    while True:
        top_up()
        drv.step()
        now = time.perf_counter()
        tracer.after_step(now)
        if now >= end:
            break
    rec.window1 = now
    tracer.close()


def drive_open(drv: Driver, gen, seconds: float, tracer) -> None:
    """Open loop: lead-in from -lead_in_s, the window [0, seconds), then
    the drain.  Arrivals due at or after ``seconds`` are never sent."""
    rec, eng = drv.rec, drv.engine
    t0 = time.perf_counter()
    w0 = t0 + gen.lead_in_s
    heap = []
    for i, (t, spec) in enumerate(gen.schedule(seconds)):
        heap.append((w0 + t, i, spec))
    heapq.heapify(heap)
    seq = len(heap)
    w1 = w0 + seconds
    drain_end = rec.drain_end = w1 + gen.drain_limit_s
    drv.phase = "lead"
    started = False
    while True:
        now = time.perf_counter()
        if not started and now >= w0:
            drv.phase, started = "window", True
            rec.window0 = w0
            tracer.start(now)
        if started and drv.phase == "window" and now >= w1:
            drv.phase = "drain"
            rec.window1 = now
            tracer.close()
        while heap and heap[0][0] <= now:
            due, _, spec = heapq.heappop(heap)
            drv.submit(spec, due=due, attempted=w0 <= due < w1)
        if drv.phase == "drain":
            left = [r for r in drv.inflight.values() if r.attempted]
            if not left or now >= drain_end:
                break
        if eng.pending:
            for r in drv.step():
                for due_rel, spec in gen.on_finish(r.spec,
                                                   r.request.generated,
                                                   r.done - w0):
                    if due_rel < seconds:
                        seq += 1
                        heapq.heappush(heap, (w0 + due_rel, seq, spec))
            tracer.after_step(time.perf_counter())
        else:
            nxt = heap[0][0] if heap else (w1 if drv.phase != "drain"
                                           else drain_end)
            if drv.phase == "lead":
                nxt = min(nxt, w0)
            elif drv.phase == "window":
                nxt = min(nxt, w1)
            with drv.annotate("chipbench.wait"):
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))


class Tracer:
    """Marks the window's opening and closing (``on_start``, ``on_close``)
    and profiles the first TRACE_SECONDS of the window (``--trace 1``)."""

    def __init__(self, drv: Driver | None, enabled: bool, on_start=None,
                 on_close=None):
        self.drv, self.enabled = drv, enabled
        self.on_start, self.on_close = on_start, on_close
        self.dir = self.path = None
        self.t_end = 0.0
        self._span = None

    def start(self, now: float) -> None:
        if self.on_start is not None:
            self.on_start()
        if not self.enabled:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness spans suffice
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("chipbench.window")
        self._span.__enter__()
        self.t_end = now + TRACE_SECONDS
        self.drv.traced = True

    def close(self) -> None:
        """The window has closed."""
        if self.on_close is not None:
            self.on_close()
        self.stop()

    def after_step(self, now: float) -> None:
        if self.drv is not None and self.drv.traced and now >= self.t_end:
            self.stop()

    def stop(self) -> None:
        if self.drv is None or not self.drv.traced:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.drv.traced = False
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    self.path = os.path.join(base, f)

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of all ``values``, interpolated
    linearly between order statistics."""
    return float(np.percentile(values, q)) if len(values) else math.nan


def end_to_end(rec: Record, setup_s: float) -> dict:
    out = {"setup_s": setup_s}
    win = rec.window_steps()
    tokens = sum(n for s in win for _, n in s.tokens.values())
    out["output_tok_s"] = tokens / rec.window_s if rec.window_s > 0 \
        else math.nan
    att = [r for r in rec.reqs.values() if r.attempted]
    if att:
        # a request that never got a token waited at least until the
        # drain gave up
        out["ttft_p95_ms"] = percentile(
            [((r.first if r.first is not None else rec.drain_end) - r.due)
             * 1e3 for r in att], 95)
        tpot = [(r.last - r.first) * 1e3 / (r.n - 1) for r in att
                if r.done is not None and r.n > 1]
        out["tpot_p95_ms"] = percentile(tpot, 95)
    return out


# ---------------------------------------------------------------------------
# the check: served tokens against the plain reference
# ---------------------------------------------------------------------------

def check_sample(rec: Record, rng: np.random.Generator) -> list[Req]:
    """Finished requests to compare: the longest one, then others drawn
    from the seed until CHECK_TOKENS served tokens and CHECK_MIN requests,
    or CHECK_REQUESTS."""
    pool = [r for r in rec.reqs.values() if r.done is not None
            and (r.attempted or any(r.spec.uid in s.tokens
                                    for s in rec.window_steps()))]
    if not pool:
        return []
    pool.sort(key=lambda r: r.spec.uid)
    longest = max(pool, key=lambda r: (r.n, -r.spec.uid))
    out, total = [longest], longest.n
    for i in rng.permutation(len(pool)):
        if (total >= CHECK_TOKENS and len(out) >= CHECK_MIN) \
                or len(out) >= CHECK_REQUESTS:
            break
        if pool[i] is not longest:
            out.append(pool[i])
            total += pool[i].n
    return out


def sample_gaps(rec: Record, params, sample: list[Req],
                gap_fn=R.served_gaps) -> np.ndarray:
    """The gaps ``gap_fn`` reads at every served token of the sample: the
    program's against the reference (``served_gaps``), or the control's
    (``control_gaps``)."""
    gaps = [gap_fn(rec.model, params, r.spec.prompt, r.request.generated)
            for r in sample]
    return np.concatenate(gaps) if gaps else np.zeros(0)


def wrong_answers(rec: Record) -> int:
    """Finished requests with the wrong number of tokens or an id outside
    the vocabulary."""
    vocab = rec.model["vocab"]
    return sum(1 for r in rec.reqs.values() if r.done is not None and (
        r.n != r.spec.max_new_tokens
        or any(not 0 <= t < vocab for t in r.request.generated)))


def check(rec: Record, params, sample: list[Req], limit: float,
          gap_fn=R.served_gaps) -> dict:
    """The numbers compared, each beside its limit.  ``gap_fn`` puts the
    control in the program's place (``chipbench/calibrate.py``)."""
    gaps = sample_gaps(rec, params, sample, gap_fn)
    log(f"[check] {len(sample)} requests, {len(gaps)} served tokens "
        f"compared with the float32 reference")
    return {"widest_logit_gap": {"value": float(gaps.max()) if len(gaps)
                                 else 0.0, "limit": limit},
            "wrong_length_or_id": {"value": wrong_answers(rec), "limit": 0},
            "tokens_compared": {"value": len(gaps), "limit": 1}}


def check_ok(numbers: dict) -> bool:
    return (numbers["widest_logit_gap"]["value"]
            <= numbers["widest_logit_gap"]["limit"]
            and numbers["wrong_length_or_id"]["value"] == 0
            and numbers["tokens_compared"]["value"] >= 1)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def model_config(conf: dict):
    from repro.configs.registry import get_model_config
    return dataclasses.replace(get_model_config(conf["arch"]),
                               **conf["model"])


@dataclasses.dataclass
class Setup:
    """What set-up builds: the device, the engine over the seed's weights,
    the traffic generator and the driver, with the compile clock running."""

    devices: list
    clock: CompileClock
    rec: Record
    params: object
    engine: object
    gen: object
    drv: Driver


def build(spec: dict, seed: int, seconds: float, t_start: float,
          peaks: dict | None = None, require_tpu: bool = True) -> Setup:
    """Set-up up to the lead-in: weights, engine, every program warmed."""
    import jax

    from repro.configs.registry import get_run_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.serving.engine import Request, ServeEngine
    from repro.sharding import RULE_SETS

    from chipbench import weights

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise RuntimeError(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < spec["cell"]["chips"]:
        raise RuntimeError(f"the cell needs {spec['cell']['chips']} chips, "
                           f"JAX sees {len(devices)}")
    peaks = peaks if peaks is not None else load_peaks(dev.device_kind)

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    conf, tparams = spec["conf"], spec["traffic"]
    serve = conf["serve"]
    cfg = model_config(conf)
    rec = Record(name=spec["cell"]["name"], conf=conf,
                 model=R.model_view(conf), serve=serve, peaks=peaks,
                 seed=seed, seconds=seconds)
    print(f"[setup] {rec.name}: device {dev.device_kind} x {len(devices)}; "
          f"compile cache {cache}", flush=True)

    run_cfg = get_run_config(conf["arch"], remat="none",
                             param_dtype=serve["param_dtype"])
    ctx = Ctx(run_cfg, RULE_SETS[run_cfg.serve_rules_name], None)
    params = weights.draw(lm.model_decls(cfg), seed, run_cfg.param_dtype)
    jax.block_until_ready(params)
    engine = ServeEngine(
        cfg, run_cfg, ctx, params, batch_size=serve["batch"],
        max_seq=serve["max_seq"], prefill_chunk=serve["prefill_chunk"],
        decode_chunk=serve["decode_chunk"], paged=True,
        block_size=serve["block_size"], n_blocks=serve["n_blocks"],
        prefix_sharing=serve["prefix_sharing"])
    gen = traffic.make(tparams, seed, cfg.vocab, serve["batch"],
                       serve["max_seq"])
    drv = Driver(engine, rec, Request, jax.profiler.TraceAnnotation)
    warm_up(drv, serve["prefill_chunk"], serve["decode_chunk"], cfg.vocab,
            uid0=-(1 << 30), shares_prefixes=tparams["kind"] == "sessions")
    print(f"[setup] warm-up done at {time.perf_counter() - t_start:.3f} s; "
          f"backend compile seconds {clock.seconds:.3f} "
          f"({clock.compiles} programs)", flush=True)
    return Setup(devices=devices, clock=clock, rec=rec, params=params,
                 engine=engine, gen=gen, drv=drv)


def run(spec: dict, seed: int, seconds: float, trace: bool,
        t_start: float, peaks: dict | None = None,
        require_tpu: bool = True) -> dict:
    """One run of the cell ``spec`` (``load_cell``); returns the result
    object the last stdout line prints."""
    su = build(spec, seed, seconds, t_start, peaks, require_tpu)
    devices, clock, rec, params = su.devices, su.clock, su.rec, su.params
    engine, gen, drv = su.engine, su.gen, su.drv
    dev, conf = devices[0], spec["conf"]

    def counters():
        return {"prefill_tokens_skipped": engine.prefill_tokens_skipped,
                "cow_copies": engine.cow_copies,
                "peak_used_blocks": engine.peak_used_blocks,
                "sync_count": engine.sync_count,
                "compiles": clock.compiles, "traces": clock.traces}

    def window_opens():
        rec.compile_s = clock.seconds
        rec.counters["start"] = counters()

    def window_closes():
        rec.counters["end"] = counters()

    tracer = Tracer(drv, trace, on_start=window_opens,
                    on_close=window_closes)
    if gen.closed:
        drive_closed(drv, gen, seconds, tracer)
    else:
        drive_open(drv, gen, seconds, tracer)
    setup_s = rec.window0 - t_start
    c0, c1 = rec.counters["start"], rec.counters["end"]
    in_window = (c1["compiles"] - c0["compiles"], c1["traces"] - c0["traces"])
    print(f"[setup] setup_s {setup_s:.3f}; backend compile seconds in "
          f"set-up {rec.compile_s:.3f}", flush=True)
    print(f"[window] compilations inside the window: {in_window[0]} "
          f"backend compiles, {in_window[1]} traces (should be 0)",
          flush=True)
    if rec.lateness:
        late = np.asarray(rec.lateness) * 1e3
        print(f"[window] generator lateness over {len(late)} requests: "
              f"median {np.median(late):.3f} ms, p95 "
              f"{np.percentile(late, 95):.3f} ms, max {late.max():.3f} ms",
              flush=True)

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    attempted = [r for r in rec.reqs.values() if r.attempted]
    if gen.closed:
        done = [r for r in rec.reqs.values() if r.done is not None
                and rec.window0 <= r.done <= rec.window1]
        n_attempted, n_failed = len(done), 0
    else:
        n_attempted = len(attempted)
        n_failed = sum(1 for r in attempted if r.done is None)

    # free the engine (pool, lanes, programs' buffers) before the reference
    drv.engine = su.engine = None
    del engine
    gc.collect()
    if tracer.path:
        from chipbench import scopes
        rec.trace = scopes.reduce(tracer.path)
    tracer.cleanup()

    sample = check_sample(rec, traffic.rng_for(seed, 7))
    t_ref = time.perf_counter()
    numbers = check(rec, params, sample, conf["check"]["widest_logit_gap"])
    log(f"[check] reference seconds {time.perf_counter() - t_ref:.3f}")

    e2e = end_to_end(rec, setup_s)
    out_metrics = {}
    if trace:
        for m in spec["per_layer"]:
            val = metrics.read(m["name"], rec)
            if val is not None:
                out_metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            out_metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    for name, v in sorted(e2e.items()):
        log(f"[result] {name} {v}")
    result = {
        "correct": check_ok(numbers),
        "attempted": n_attempted,
        "failed": n_failed,
        "metrics": out_metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace and rec.trace is not None:
        result["device"]["busy_s"] = rec.trace.busy_s()
        result["device"]["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                               "idle_gaps": rec.trace.idle_gaps(10)}
    for name, v in numbers.items():
        log(f"[check] {name} {v['value']} limit {v['limit']}")
    result["check"] = numbers
    return result
