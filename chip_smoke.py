"""Bring-up smoke of this repo on a TPU: the quickest proof that the
system still starts on the chip.

  python3 chip_smoke.py             # one chip: serving
  python3 chip_smoke.py --chips 4   # a four-chip host: sharded training

One chip: every main-path Pallas kernel is checked against its ``ref.py``
oracle at minitron-4b's widths, then minitron-4b is built at its published
widths and full depth (seeded random bf16 weights) and serves requests —
some sharing a system prefix, so prefix mapping and copy-on-write both
run — through ``ServeEngine(paged=True, prefix_sharing=True)``, with the
block pool filling the device memory the weights leave.

Four chips: zamba2-1.2b at its published widths, cut to 12 layers (two
whole shared-attention periods), takes train steps through
``make_train_step`` on a (2, 2) ("data", "model") mesh, compared with the
same steps on one device of the host.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails.  The last stdout line is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times printed on the way are set-up/smoke times, not throughput.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
# serving cell: batch 8 at max_seq 4096 with 16-row pool blocks
BATCH, MAX_SEQ, BLOCK = 8, 4096, 16
PREFILL_CHUNK, DECODE_CHUNK, NEW_TOKENS = 256, 8, 48
# 136 = 8 whole blocks + 8 rows: a hit maps 8 blocks and copies the tail
PREFIX = 136
# device memory kept free of the pool for program temporaries: the decode
# step's were 1.01 GB in an ahead-of-time v5e compile (relaid-out attention
# weights), the prefill step's less
RESERVE_BYTES = 2 << 30
# the repo's sharded-vs-single-device loss tolerance (tests/test_sharding.py)
LOSS_TOL = 5e-2


class Report:
    """Collects named checks; each prints as it is made."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str) -> None:
        print(f"[check] {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def device_memory() -> dict:
    """The first device's allocator statistics (bytes_limit, bytes_in_use,
    peak_bytes_in_use)."""
    import jax
    return jax.devices()[0].memory_stats()


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache hits
    excluded), read from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


# ---------------------------------------------------------------------------
# one chip: kernels against their oracles, then the paged serving engine
# ---------------------------------------------------------------------------

def _bf16_normal(key, shape):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.bfloat16)


def check_kernels(report: Report, cfg) -> None:
    """Each main-path kernel against its ref.py oracle at ``cfg``'s head
    widths, on bf16 inputs.

    Attention bound, 2^-7 * max|v|: both sides form the same f32 logits
    from the same bf16 q/k; the kernel rounds the softmax probabilities to
    bf16 (8-bit significand) before the PV product, at most 2^-9 relative
    each, so at most 2^-9 * max|v| on a convex combination of v rows; both
    sides round the output to bf16, 2^-9 * max|v| each.  2^-7 leaves 4/3 x
    that sum.  The cache write is a copy: bound 0."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import (cache_update_paged,
                                               flash_attention,
                                               flash_decode_paged)

    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    S = 2048
    q = _bf16_normal(next(keys), (1, S, H, D))
    k = _bf16_normal(next(keys), (1, S, K, D))
    v = _bf16_normal(next(keys), (1, S, K, D))
    bound = 2.0 ** -7 * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    e = err(flash_attention(q, k, v), ref.attention_naive(q, k, v))
    report.check("flash_attention vs ref.attention_naive", e <= bound,
                 f"max err {e:.6g} <= bound {bound:.6g}, S={S}")

    # a two-layer stack read at layer 1 — the engine's call — over a
    # shuffled pool with ragged kv_len
    max_blocks, L = 64, 2
    n_blocks = BATCH * max_blocks + 1
    kp = _bf16_normal(next(keys), (L, n_blocks, BLOCK, K, D))
    vp = _bf16_normal(next(keys), (L, n_blocks, BLOCK, K, D))
    tables = jax.random.permutation(next(keys), n_blocks)[
        :BATCH * max_blocks].reshape(BATCH, max_blocks).astype(jnp.int32)
    bound = 2.0 ** -7 * float(jnp.max(jnp.abs(vp[1].astype(jnp.float32))))
    for sq in (1, PREFILL_CHUNK):
        lens = jax.random.randint(next(keys), (BATCH,), sq,
                                  max_blocks * BLOCK + 1, jnp.int32)
        qd = _bf16_normal(next(keys), (BATCH, sq, H, D))
        got = flash_decode_paged(qd, kp, vp, lens, tables, 1)
        want = ref.decode_attention_paged_ref(qd, kp[1], vp[1], lens, tables)
        e = err(got, want)
        report.check(f"flash_decode_paged vs oracle, Sq={sq}", e <= bound,
                     f"max err {e:.6g} <= bound {bound:.6g}")

    for sn in (1, PREFILL_CHUNK):
        idx = jax.random.randint(next(keys), (BATCH,), 0,
                                 max_blocks * BLOCK - sn + 1, jnp.int32)
        idx = idx.at[0].set(max_blocks * BLOCK)      # a done slot drops
        kn = _bf16_normal(next(keys), (BATCH, sn, K, D))
        vn = _bf16_normal(next(keys), (BATCH, sn, K, D))
        want_k, want_v = ref.kv_cache_update_paged_ref(kp[1], vp[1], kn, vn,
                                                       idx, tables)
        got_k, got_v = cache_update_paged(kp, vp, kn, vn, idx, tables, 1)
        e = max(err(got_k[1], want_k), err(got_v[1], want_v),
                err(got_k[0], kp[0]))
        report.check(f"cache_update_paged vs oracle, Sn={sn}", e == 0.0,
                     f"max err {e:.6g} == bound 0, layer 0 untouched")


def check_model_logits(report: Report, cfg, run, params) -> None:
    """The whole model's chunked paged prefill with the Pallas kernels
    against the same program on the jnp reference ops: last-position
    logits on one 2-chunk prompt.  Bound 2^-3 * max|reference logit|: the
    kernels differ from the oracles by bf16 roundings (check_kernels),
    which the layers carry forward; a wrong mask, head mapping or cache
    row gives errors of the logits' own size."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.serving.engine import make_prefill_chunk_step_paged
    from repro.sharding import RULE_SETS

    n = 2 * PREFILL_CHUNK
    rng = np.random.default_rng(SEED + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, n)), jnp.int32)
    rules = RULE_SETS[run.serve_rules_name]
    out = {}
    for mode in (None, "reference"):
        ctx = Ctx(run.replace(kernel_mode=mode), rules, None)
        cache = lm.init_paged_cache(ctx, cfg, 1, n, BLOCK)
        cache["block_tables"] = jnp.arange(n // BLOCK, dtype=jnp.int32)[None]
        step = jax.jit(make_prefill_chunk_step_paged(cfg, ctx.run, ctx),
                       donate_argnums=(1,))
        for i in range(0, n, PREFILL_CHUNK):
            cache, logits = step(params, cache, toks[:, i:i + PREFILL_CHUNK],
                                 0, i)
        out[mode] = logits
        del cache
    scale = float(jnp.max(jnp.abs(out["reference"])))
    e = float(jnp.max(jnp.abs(out[None] - out["reference"])))
    same = bool(jnp.argmax(out[None]) == jnp.argmax(out["reference"]))
    report.check("model prefill logits, Pallas vs reference ops",
                 bool(np.isfinite(e)) and e <= 2.0 ** -3 * scale,
                 f"max err {e:.6g} <= bound {2.0 ** -3 * scale:.6g}; "
                 f"argmax agrees: {same}")


def serve(report: Report, arch: str, cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_run_config
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.models.params import init_params, param_count
    from repro.serving.engine import Request, ServeEngine
    from repro.sharding import RULE_SETS

    # serving keeps no f32 masters: weights are drawn in the compute dtype
    run = get_run_config(arch, remat="none", param_dtype="bfloat16")
    ctx = Ctx(run, RULE_SETS[run.serve_rules_name], None)
    t0 = time.perf_counter()
    decls = lm.model_decls(cfg)
    params = init_params(decls, jax.random.PRNGKey(SEED), run.param_dtype)
    jax.block_until_ready(params)
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, vocab "
          f"{cfg.vocab}; {param_count(decls):,} parameters, parameter "
          f"bytes {sum(a.nbytes for a in jax.tree.leaves(params)):,} "
          f"({run.param_dtype}); init {time.perf_counter() - t0:.1f}s "
          f"(set-up)", flush=True)

    check_model_logits(report, cfg, run, params)

    stats = device_memory()
    per_block = 2 * cfg.n_layers * BLOCK * cfg.n_kv_heads * cfg.head_dim \
        * jnp.dtype(run.compute_dtype).itemsize
    n_blocks = int((stats["bytes_limit"] - stats["bytes_in_use"]
                    - RESERVE_BYTES) // per_block) - 1   # - parking block
    need = BATCH * MAX_SEQ // BLOCK
    print(f"[serve] pool: {n_blocks} blocks of {BLOCK} rows "
          f"({n_blocks * per_block:,} bytes; dense capacity is {need} "
          f"blocks); device bytes_in_use {stats['bytes_in_use']:,} of "
          f"bytes_limit {stats['bytes_limit']:,}", flush=True)

    rng = np.random.default_rng(SEED)
    system = rng.integers(0, cfg.vocab, PREFIX).tolist()
    own = lambda n: rng.integers(0, cfg.vocab, n).tolist()
    # chunk_plan sizes stay in {256, 128}: 512 -> 256+256 and the shared
    # requests' 384-token suffixes -> 256+128
    reqs = [Request(uid=0, prompt=system + own(512 - PREFIX),
                    max_new_tokens=NEW_TOKENS, prefix_len=PREFIX)]
    reqs += [Request(uid=i, prompt=system + own(384),
                     max_new_tokens=NEW_TOKENS, prefix_len=PREFIX)
             for i in (1, 2, 3)]
    reqs += [Request(uid=i, prompt=own(512 if i % 2 else 384),
                     max_new_tokens=NEW_TOKENS) for i in (4, 5, 6, 7)]

    engine = ServeEngine(cfg, run, ctx, params, batch_size=BATCH,
                         max_seq=MAX_SEQ, prefill_chunk=PREFILL_CHUNK,
                         decode_chunk=DECODE_CHUNK, paged=True,
                         block_size=BLOCK, n_blocks=n_blocks,
                         prefix_sharing=True)
    t0 = time.perf_counter()
    done = engine.generate(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {n_tok} tokens generated, "
          f"{engine.sync_count} host syncs; smoke time {wall:.1f}s "
          f"(includes compilation); compile seconds so far "
          f"{clock.seconds:.1f}", flush=True)
    report.check("every request served",
                 sorted(r.uid for r in done) == list(range(len(reqs)))
                 and all(len(r.generated) == NEW_TOKENS for r in done)
                 and all(0 <= t < cfg.vocab for r in done
                         for t in r.generated),
                 f"{len(done)}/{len(reqs)} requests x {NEW_TOKENS} tokens "
                 f"in [0, vocab)")
    report.check("prefix sharing mapped and copied",
                 engine.prefill_tokens_skipped == 3 * PREFIX
                 and engine.cow_copies == 3,
                 f"{engine.prefill_tokens_skipped} prompt rows skipped, "
                 f"{engine.cow_copies} copy-on-write blocks")

    text = engine._decode_fn.lower(
        engine.params, engine._cache, engine._cur, engine._index,
        engine._rem, engine._done).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    report.check("decode step runs Pallas kernels", n_calls > 0,
                 f"{n_calls} tpu_custom_call mentions in the compiled step")

    stats = device_memory()
    peak = stats.get("peak_bytes_in_use", 0)
    share = peak / stats["bytes_limit"]
    report.check("pool fills the device", share >= 0.70,
                 f"peak_bytes_in_use {peak:,} = {share:.3f} of bytes_limit")


# ---------------------------------------------------------------------------
# four chips: sharded training against one device
# ---------------------------------------------------------------------------

def train_losses(cfg, run, ctx, batches, shardings=None) -> list[float]:
    """Losses of ``len(batches)`` train steps from the seeded init."""
    import jax
    from repro.train.step import init_state, make_train_step

    state = init_state(cfg, run, jax.random.PRNGKey(SEED)).tree()
    if shardings is not None:
        state = jax.device_put(state, shardings)
    step = jax.jit(make_train_step(cfg, run, ctx), donate_argnums=(0,))
    losses = []
    for batch in batches:
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def check_sharded_training(report: Report, arch: str, cfg, batch: int,
                           seq: int, mesh_shape=(2, 2)) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_run_config
    from repro.data.pipeline import DataConfig, TokenSource
    from repro.launch.mesh import make_mesh_for
    from repro.models.layers import Ctx
    from repro.sharding import RULE_SETS, tree_shardings
    from repro.train.step import abstract_state, state_logical_axes

    run = get_run_config(arch, total_steps=2, remat="full",
                         logits_chunk=min(seq, 1024))
    rules = RULE_SETS[run.rules_name]
    data = TokenSource(DataConfig(vocab=cfg.vocab, global_batch=batch,
                                  seq_len=seq, seed=SEED))
    batches = [{k: jnp.asarray(v) for k, v in data.batch(i).items()}
               for i in range(2)]
    t0 = time.perf_counter()
    one = train_losses(cfg, run, Ctx(run, rules, None), batches)
    mesh = make_mesh_for(mesh_shape, ("data", "model"))
    sh = tree_shardings(rules, mesh, state_logical_axes(cfg),
                        abstract_state(cfg, run))
    sharded = train_losses(cfg, run, Ctx(run, rules, mesh), batches, sh)
    print(f"[train] {cfg.name} at {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, batch {batch} x seq {seq}; mesh {mesh_shape} "
          f"('data', 'model'); smoke time {time.perf_counter() - t0:.1f}s "
          f"(includes compilation)", flush=True)
    for i, (a, b) in enumerate(zip(sharded, one)):
        report.check(f"step {i} loss, sharded vs one device",
                     abs(a - b) <= LOSS_TOL,
                     f"sharded {a:.6f}, one device {b:.6f}, "
                     f"|diff| {abs(a - b):.3g} <= tol {LOSS_TOL}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.configs.registry import get_model_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[setup] compile cache {enable_compile_cache()}; device "
          f"{devices[0].device_kind} x {len(devices)}", flush=True)
    clock = CompileClock()
    report = Report()
    if args.chips == 4:
        # two whole shared-attention periods (6 Mamba layers each)
        cfg = dataclasses.replace(get_model_config("zamba2-1.2b"),
                                  n_layers=12)
        check_sharded_training(report, "zamba2-1.2b", cfg, batch=8, seq=512)
    else:
        cfg = get_model_config("minitron-4b")
        check_kernels(report, cfg)
        serve(report, "minitron-4b", cfg, clock)
    print(f"[setup] compile seconds {clock.seconds:.1f}", flush=True)
    if report.failed:
        print(f"chip_smoke: failed checks: {report.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
