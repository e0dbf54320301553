"""Serving: continuous-batching runtime over per-slot cache state.

Three device programs make up the runtime (all shapes fixed — no
per-prompt-length retraces):

  * ``make_prefill_chunk_step``: one power-of-two prompt chunk prefills
    into ONE slot's cache rows (the slot is sliced out, run at batch=1,
    scattered back), while every other slot's state is untouched.  A
    prompt of any length is a ``chunk_plan`` of these.
  * ``make_decode_chunk_step``: a device-resident ``lax.while_loop`` over
    K decode steps for the WHOLE batch with a per-slot cache-index vector
    ``(B,)`` and per-slot done flags — one host sync per K-token chunk
    instead of one per token.  Finished (and empty) slots are masked by
    the done flags: their writes drop (index = max_seq) and they emit no
    tokens.
  * an admission step that installs a freshly prefilled request into its
    slot's lane of the running decode state.

``make_prefill_step`` / ``make_decode_step`` remain the single-shot
whole-batch programs (``decode_*`` / ``long_*`` dry-run cells lower
``make_decode_step``; ``prefill_*`` cells lower ``make_prefill_step``).

When a ``repro.power.PowerManager`` is attached, prefill and decode run
under their own phase caps — the serving form of the paper's per-task
capping (compute-bound prefill keeps a high cap, memory-bound decode a
low one).  Phases are entered at CHUNK granularity: one ``phase("decode",
calls=K)`` per K-token chunk amortizes the cap write, the wall-clock
reads and the EWMA ``observe()`` over K tokens.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.core.tasks import Task
from repro.models import lm
from repro.models.layers import Ctx
from repro.obs.tracer import NULL_TRACER
from repro.serving.scheduler import (BlockAllocator, PrefixRegistry, Request,
                                     SlotScheduler, chunk_plan,
                                     fewest_remaining)

__all__ = ["Request", "ServeEngine", "SlotSnapshot", "serve_phase_tasks",
           "fewest_remaining", "make_prefill_step", "make_decode_step",
           "make_prefill_chunk_step", "make_prefill_chunk_step_paged",
           "make_decode_chunk_step", "BlockAllocator", "PrefixRegistry"]


def serve_phase_tasks(cfg: ModelConfig, batch: int, prompt: int,
                      new_tokens: int, chips: int = 1) -> list[Task]:
    """Prefill vs decode phases with analytic roofline terms — the serving
    analogue of ``train.phases.training_phase_tasks``.  Prefill is
    compute-bound (wants a high cap per SED); decode streams the KV cache
    (memory-bound — a low cap is nearly free)."""
    from repro.hw import flops as F
    n = F.active_param_count(cfg)
    prefill_flops = 2.0 * n * batch * prompt \
        + F._attention_flops_fwd(cfg, batch, prompt, prompt)
    decode_flops = 2.0 * n * batch
    cache = F._cache_bytes(cfg, batch, prompt)
    return [
        Task("prefill", flops=prefill_flops / chips,
             hbm_bytes=(2.0 * n + cache) / chips),
        Task("decode", flops=decode_flops / chips,
             hbm_bytes=(2.0 * n + cache) / chips, calls=new_tokens),
    ]


# ===========================================================================
# single-shot whole-batch programs (dry-run cells, equivalence tests)
# ===========================================================================

def make_prefill_step(cfg: ModelConfig, run: RunConfig, ctx: Ctx,
                      max_seq: int):
    """prefill(params, tokens_batch) -> (cache, last_logits)."""

    def prefill(params, batch):
        B = (batch["frames"].shape[0] if cfg.family == "audio"
             else batch["tokens"].shape[0])
        if cfg.family == "audio":
            # encoder: no cache; "prefill" = full encode, return all logits
            h, _, _ = lm.forward(ctx, cfg, params, batch)
            return None, lm.logits_for(ctx, cfg, params, h[:, -1:, :])
        cache = lm.init_cache(ctx, cfg, B, max_seq)
        h, _, new_cache = lm.forward(ctx, cfg, params, batch,
                                     cache=cache, cache_index=0)
        logits = lm.logits_for(ctx, cfg, params, h[:, -1:, :])
        return new_cache, logits

    return prefill


def make_decode_step(cfg: ModelConfig, run: RunConfig, ctx: Ctx):
    """decode(params, cache, tokens (B,1), index ()) -> (cache, logits)."""

    def decode(params, cache, tokens, index):
        batch = {"tokens": tokens}
        if cfg.mrope_sections is not None:
            B = tokens.shape[0]
            pos = jnp.broadcast_to(index.astype(jnp.int32), (3, B, 1))
            batch["positions"] = pos
        h, _, new_cache = lm.forward(ctx, cfg, params, batch,
                                     cache=cache, cache_index=index)
        logits = lm.logits_for(ctx, cfg, params, h)
        return new_cache, logits[:, 0]

    return decode


# ===========================================================================
# continuous-batching device programs
# ===========================================================================

def _slice_slot(tree, slot):
    """One slot's lane of a stacked cache tree (batch axis = 1)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), tree)


def _merge_slot(tree, sub, slot):
    return jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_slice_in_dim(
            full, new.astype(full.dtype), slot, axis=1), tree, sub)


def make_prefill_chunk_step(cfg: ModelConfig, run: RunConfig, ctx: Ctx):
    """prefill_chunk(params, cache, tokens (1,chunk), slot (), index ())
    -> (cache, logits (1,V)).

    Writes the chunk's KV rows / SSM state into ONE slot of the shared
    batch cache; every other slot is untouched, so the rest of the batch
    can keep decoding between chunks.  Under jit this traces once per
    chunk SIZE (a power of two from ``chunk_plan``), never per prompt
    length."""

    def prefill_chunk(params, cache, tokens, slot, index):
        sub = _slice_slot(cache, slot)
        h, _, sub = lm.forward(ctx, cfg, params, {"tokens": tokens},
                               cache=sub, cache_index=index)
        logits = lm.logits_for(ctx, cfg, params, h[:, -1:, :])
        return _merge_slot(cache, sub, slot), logits[:, 0]

    return prefill_chunk


def make_prefill_chunk_step_paged(cfg: ModelConfig, run: RunConfig, ctx: Ctx):
    """Paged-cache variant of ``make_prefill_chunk_step``.

    Block pools have no batch axis, so the dense slice-lane/merge-lane
    trick cannot isolate one slot.  Instead the pools are passed WHOLE
    with only the slot's block-table row (and, for hybrids, its recurrent
    state lane): the paged scatter writes exclusively into blocks that
    row maps, so every other slot's blocks are untouched — the same
    isolation, enforced by block ownership instead of lane slicing."""
    spec = lm.cache_slot_spec(cfg)

    def prefill_chunk(params, cache, tokens, slot, index):
        sub = {}
        for key, leaf in cache.items():
            if key == "block_tables":
                sub[key] = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)
            elif spec.get(key) == lm.SLOT_STATE:
                sub[key] = _slice_slot(leaf, slot)
            else:
                sub[key] = leaf                     # pool: passed whole
        h, _, new_sub = lm.forward(ctx, cfg, params, {"tokens": tokens},
                                   cache=sub, cache_index=index)
        logits = lm.logits_for(ctx, cfg, params, h[:, -1:, :])
        out = {}
        for key in cache:
            if key == "block_tables":
                out[key] = cache[key]               # table rows are host-set
            elif spec.get(key) == lm.SLOT_STATE:
                out[key] = _merge_slot(cache[key], new_sub[key], slot)
            else:
                out[key] = new_sub[key]
        return out, logits[:, 0]

    return prefill_chunk


def make_decode_chunk_step(cfg: ModelConfig, run: RunConfig, ctx: Ctx,
                           chunk: int, max_seq: int):
    """decode_chunk(params, cache, cur, index, rem, done) ->
    (cache, cur, index, rem, done, out (B,chunk), steps ()).

    Device-resident ``lax.while_loop`` over up to ``chunk`` tokens with
    per-slot state vectors (B,): ``cur`` is each slot's newest
    not-yet-delivered token, ``index`` its cache write offset, ``rem``
    tokens still owed, ``done`` the mask for finished/empty slots.  The
    loop exits early when every slot is done.  ``out`` collects emitted
    tokens (-1 where a slot was done) — the ONLY value the host needs per
    chunk, so serving costs one device_get per chunk, not per token."""

    def decode_chunk(params, cache, cur, index, rem, done):
        B = cur.shape[0]
        out0 = jnp.full((B, chunk), -1, jnp.int32)

        def cond(st):
            _, _, _, _, done, _, t = st
            return (t < chunk) & ~jnp.all(done)

        def body(st):
            cache, cur, index, rem, done, out, t = st
            # deliver each live slot's pending token into the out buffer
            out = out.at[:, t].set(jnp.where(done, -1, cur))
            rem = jnp.where(done, rem, rem - 1)
            done = done | (rem <= 0)
            # done slots write at max_seq: OOB rows are DROPPED by the
            # per-slot cache scatter, so retired lanes cost no state
            widx = jnp.where(done, max_seq, index)
            h, _, cache = lm.forward(
                ctx, cfg, params, {"tokens": cur[:, None]},
                cache=cache, cache_index=widx)
            logits = lm.logits_for(ctx, cfg, params, h)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            cur = jnp.where(done, 0, nxt)
            index = jnp.where(done, index, index + 1)
            return (cache, cur, index, rem, done, out, t + 1)

        st = (cache, cur.astype(jnp.int32), index.astype(jnp.int32),
              rem.astype(jnp.int32), done, out0, jnp.asarray(0, jnp.int32))
        return jax.lax.while_loop(cond, body, st)

    return decode_chunk


def _install_step(cur, index, rem, done, tok, slot, offset, budget):
    """Arm one slot's decode lane: ``tok`` is the pending (not yet
    delivered, not yet cache-written) token, ``offset`` the slot's cache
    write position, ``budget`` the tokens still owed.  Shared by fresh
    admission (tok from the prefill logits, offset = prompt length) and
    snapshot restore (tok/offset/budget from the drained cursor)."""
    cur = cur.at[slot].set(tok)
    index = index.at[slot].set(offset)
    rem = rem.at[slot].set(budget)
    done = done.at[slot].set(budget <= 0)
    return cur, index, rem, done


def _admit_step(cur, index, rem, done, logits, slot, plen, max_new):
    """Install a freshly prefilled request into its slot's decode lane:
    first generated token from the prefill logits, cache offset at the
    prompt length, token budget armed."""
    first = jnp.argmax(logits[0]).astype(jnp.int32)
    return _install_step(cur, index, rem, done, first, slot, plen, max_new)


@dataclasses.dataclass
class SlotSnapshot:
    """One request's portable in-flight state — everything another
    engine needs to continue the stream bit-identically.

    Decoding is greedy (RNG-free), so the cursor is just ``cur`` — the
    PENDING token: computed, but not yet delivered to the request nor
    written to the cache (delivery and the cache write both happen at
    the next decode iteration) — plus ``kv_len`` (rows valid = prompt +
    written tokens) and ``rem`` (tokens still owed).  ``payload`` is the
    ``repro.models.lm.export_slot`` cache lane; ``None`` marks a COLD
    snapshot (request never admitted — restoring simply re-queues it for
    ordinary prefill admission)."""

    request: Request
    rem: int
    kv_len: int = 0
    cur: int | None = None
    payload: dict | None = None
    #: Leading rows NOT in the payload (a prefix-shared slot ships only
    #: its private suffix).  The restoring engine rebuilds rows
    #: [0, prefix_len) from its own prefix registry — or, on a miss /
    #: dense engine, by re-prefilling ``request.prompt[:prefix_len]`` —
    #: BEFORE arming the cursor.  0 = self-contained payload.
    prefix_len: int = 0

    @property
    def warm(self) -> bool:
        return self.payload is not None

    @property
    def payload_bytes(self) -> int:
        """On-wire cost of migrating this snapshot (cache lane only —
        the host-side fields are negligible next to it)."""
        return lm.slot_payload_bytes(self.payload) if self.warm else 0


def _reset_mamba_slot(cache, slot):
    """Zero one slot's recurrent (SSM + conv) state before reuse: unlike
    KV rows, which are masked by per-slot kv_len, Mamba state carries
    unconditionally and would leak the previous request into the next."""
    def zero_lane(a):
        lane = jnp.zeros_like(jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1))
        return jax.lax.dynamic_update_slice_in_dim(a, lane, slot, axis=1)
    return dict(cache, mamba=jax.tree.map(zero_lane, cache["mamba"]))


class ServeEngine:
    """Continuous-batching serving runtime (greedy decoding).

    ``batch_size`` device-resident slots each hold one in-flight request
    at its own cache offset.  Admission happens at any step regardless of
    prompt length (chunked per-slot prefill — no equal-length bucketing,
    no per-length retrace); decode runs as a device-resident loop over
    ``decode_chunk``-token chunks with ONE host sync per chunk; a slot is
    recycled the moment its request finishes, at chunk granularity.

    With a ``repro.power.PowerManager`` attached, prefill and decode run
    under their own phase caps, entered once per admission round / decode
    chunk (chunk-amortized ``observe()``).

    Two driving styles: ``generate(requests)`` runs to drain, while
    ``start(requests)`` + ``step()``-while-``pending`` exposes the same
    loop one admission-round-plus-decode-chunk at a time, so an external
    scheduler (``repro.fleet``) can interleave and preempt serving work at
    chunk granularity.

    Preemption is LOSSLESS: ``drain()`` stops the stream and returns every
    request as a ``SlotSnapshot`` (in-flight slots warm — cache lane +
    decode cursor — queued requests cold), and ``restore(snaps)`` admits
    snapshots into this or ANY other engine built from the same model
    config, including one with a different ``batch_size``/``max_seq``.
    ``start``/``step`` are thin wrappers over the same admission machinery
    — a step installs restored slots first, then prefills fresh ones.

    Preemption is also PROPORTIONAL: ``drain(slots=[...])`` sheds only the
    named slots (victims picked by ``select_victims`` under the engine's
    ``victim_policy``, default fewest-remaining-tokens-first) while every
    surviving slot keeps decoding bit-identically, and ``set_slot_limit``
    pins the shed capacity down so freed lanes don't instantly refill.

    ``snapshot_int8=True`` compresses warm payloads at rest (per-row int8
    + f32 scale — ``models.lm.quantize_payload``), roughly halving
    ``payload_bytes`` at a bounded parity cost (restores are then no
    longer bit-exact; the per-leaf error budget is documented in
    docs/fleet.md).

    ``paged=True`` swaps the dense per-slot cache for a refcounted block
    pool (``block_size`` rows per block, ``n_blocks`` blocks; default =
    dense capacity).  Every slot reserves its blocks UP FRONT at
    admission (prompt + max_new_tokens rows), so a running request can
    never be killed by pool exhaustion — admission is gated instead
    (FCFS, via the scheduler's ``can_admit`` hook).  Token streams are
    bit-identical to the dense engine.  ``prefix_sharing=True``
    additionally registers each request's ``prefix_len`` leading rows
    after prefill; later admissions whose prompts start with the same
    tokens map the cached blocks (copy-on-write on the partial tail
    block) and skip prefilling them — see docs/serving.md.
    """

    def __init__(self, cfg: ModelConfig, run: RunConfig, ctx: Ctx, params,
                 batch_size: int = 4, max_seq: int = 256, power=None,
                 prefill_chunk: int = 32, decode_chunk: int = 8,
                 snapshot_int8: bool = False, victim_policy=None,
                 tracer=None, trace_track: str = "engine",
                 paged: bool = False, block_size: int = 16,
                 n_blocks: int | None = None, prefix_sharing: bool = False):
        if cfg.family == "audio":
            raise ValueError("encoder-only arch has no decode path")
        prefill_chunk = min(prefill_chunk, max_seq)
        if prefill_chunk < 1 or prefill_chunk & (prefill_chunk - 1):
            raise ValueError(f"prefill_chunk must be a power of two, "
                             f"got {prefill_chunk}")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if prefix_sharing and not paged:
            raise ValueError("prefix_sharing requires paged=True")
        if paged:
            if cfg.family == "ssm":
                raise ValueError("ssm caches have no sequence rows to page")
            if max_seq % block_size:
                raise ValueError(f"max_seq {max_seq} must be a multiple of "
                                 f"block_size {block_size}")
            if prefix_sharing and any(
                    kind == lm.SLOT_STATE
                    for kind in lm.cache_slot_spec(cfg).values()):
                raise ValueError(
                    "prefix_sharing requires a pure-rows cache schema "
                    "(recurrent state cannot be row-shared)")
        self.cfg, self.run, self.ctx = cfg, run, ctx
        self.params = params
        self.batch_size, self.max_seq = batch_size, max_seq
        self.power = power   # Optional[repro.power.PowerManager]
        self.prefill_chunk = prefill_chunk
        self.decode_chunk = decode_chunk
        self.snapshot_int8 = snapshot_int8
        self.victim_policy = victim_policy or fewest_remaining
        self.paged, self.block_size = paged, block_size
        self.prefix_sharing = prefix_sharing
        self.max_blocks = max_seq // block_size if paged else 0
        self.n_blocks = (n_blocks if n_blocks is not None
                         else batch_size * self.max_blocks) if paged else 0
        # paged-mode counters (monotonic across drain/restore cycles)
        self.prefill_tokens_skipped = 0
        self.cow_copies = 0
        self.peak_used_blocks = 0
        # observability: spans/instants on a modeled virtual timebase
        # (``_vt`` advances by the modeled chunk runtime when a power
        # session is attached, by 1.0 per phase otherwise); default
        # NULL_TRACER is zero-cost — see repro.obs / docs/observability.md
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trace_track = trace_track
        self._vt = 0.0
        # jit caches one program per (1, chunk_size) token shape — the
        # chunk_plan power-of-two sizes bound the trace count.  Every step
        # DONATES the cache (pools, block tables) and the decode-lane
        # vectors it rewrites, so the device holds them once, not twice.
        mk = make_prefill_chunk_step_paged if paged else make_prefill_chunk_step
        self._prefill_step = jax.jit(mk(cfg, run, ctx), donate_argnums=(1,))
        self._decode_fn = jax.jit(
            make_decode_chunk_step(cfg, run, ctx, decode_chunk, max_seq),
            donate_argnums=(1, 2, 3, 4, 5))
        lanes = (0, 1, 2, 3)
        self._admit_fn = jax.jit(_admit_step, donate_argnums=lanes)
        self._install_fn = jax.jit(_install_step, donate_argnums=lanes)
        self._reset_fn = jax.jit(_reset_mamba_slot, donate_argnums=(0,))
        if paged:
            rows_keys = [k for k, v in lm.cache_slot_spec(cfg).items()
                         if v == lm.SLOT_ROWS]

            def set_table_row(table, row, sid):
                return table.at[sid].set(row)

            def copy_block(cache, src, dst):
                # CoW: duplicate pool block src -> dst in every rows-leaf
                out = dict(cache)
                for key in rows_keys:
                    out[key] = jax.tree.map(
                        lambda a: a.at[:, dst].set(a[:, src]), cache[key])
                return out

            self._table_fn = jax.jit(set_table_row, donate_argnums=(0,))
            self._copy_fn = jax.jit(copy_block, donate_argnums=(0,))
        # warm snapshots awaiting a free slot (restored ahead of fresh
        # admissions — they carry finished work)
        self._restore_q: deque[SlotSnapshot] = deque()
        # occupancy cap surviving drain/restore cycles (partial preemption
        # pins it below batch_size so shed lanes stay empty)
        self._slot_limit = batch_size
        # transfer seam: tests swap this for a counting double to assert
        # the one-sync-per-chunk contract
        self._fetch = jax.device_get
        self.sync_count = 0
        self.completion_s: dict[int, float] = {}   # uid -> wall s in generate

    # -- internals ---------------------------------------------------------
    def _phase(self, name: str, calls: int | None = None):
        if self.power is None:
            return contextlib.nullcontext()
        return self.power.phase(name, calls=calls)

    def _prefill_rows(self, tokens, sid: int, idx0: int):
        """Chunked prefill of ``tokens`` into rows [idx0, idx0 + len) of
        slot ``sid`` (mutates ``self._cache``); returns the last-token
        logits (1, V).  ``idx0 > 0`` is the prefix-shared suffix prefill
        and the restore-path prefix rebuild."""
        idx, logits = idx0, None
        for size in chunk_plan(len(tokens), self.prefill_chunk):
            o = idx - idx0
            toks = jnp.asarray([tokens[o:o + size]], jnp.int32)
            self._cache, logits = self._prefill_step(
                self.params, self._cache, toks, sid, idx)
            idx += size
        return logits

    def _prefill_into_slot(self, cache, req: Request, sid: int):
        """Chunked prefill of one request into slot ``sid``; returns the
        updated cache and the last-token logits (1, V)."""
        if "mamba" in cache:    # recurrent state carries across requests
            cache = self._reset_fn(cache, sid)
        self._cache = cache
        logits = self._prefill_rows(req.prompt, sid, 0)
        return self._cache, logits

    # -- paged-mode block bookkeeping --------------------------------------

    def _shared_credit(self, prompt, prefix_cap: int) -> int:
        """Rows a registry hit would supply for ``prompt`` right now —
        side-effect-free (the admission gate's capacity estimate)."""
        if self._registry is None or prefix_cap <= 0:
            return 0
        rows, _ = self._registry.lookup(prompt, prefix_cap, peek=True)
        return rows

    def _fits_blocks(self, prompt, total_rows: int, prefix_cap: int) -> bool:
        """Whether the pool can cover a ``total_rows``-row reservation for
        ``prompt`` — counting full shared prefix blocks as free credit and
        evicting LRU registry prefixes when the free list falls short."""
        need_full = self._alloc.blocks_for(total_rows)
        credit = self._shared_credit(prompt, prefix_cap) // self.block_size
        if self._alloc.free_blocks >= need_full - credit:
            return True
        if self._registry is not None:
            # eviction may drop the very prefix the credit counted on —
            # re-probe after, never before, trusting the stale credit
            self._registry.evict_for(need_full)
            credit = self._shared_credit(prompt, prefix_cap) \
                // self.block_size
        return self._alloc.free_blocks >= need_full - credit

    def _can_admit(self, req: Request) -> bool:
        return self._fits_blocks(
            req.prompt, len(req.prompt) + req.max_new_tokens,
            min(req.prefix_len, len(req.prompt) - 1))

    def _map_slot_blocks(self, sid: int, total_rows: int, shared_rows: int,
                         shared_blocks) -> list[int]:
        """Reserve and table-map slot ``sid``'s blocks for a
        ``total_rows``-row lifetime: full shared prefix blocks are
        reference-mapped, a partially-shared tail block is copy-on-write
        duplicated (its first write — the suffix prefill — is imminent),
        and the remainder is allocated fresh.  Returns the logical-order
        block list (also recorded in ``_slot_blocks``)."""
        bs = self.block_size
        full = shared_rows // bs
        blocks: list[int] = []
        if shared_rows:
            self._alloc.share(shared_blocks[:full])
            blocks += shared_blocks[:full]
            if shared_rows % bs:
                tail = shared_blocks[full]
                self._alloc.share([tail])           # our reference...
                priv, copied = self._alloc.ensure_private(tail)  # ...pivots
                if copied:
                    self._cache = self._copy_fn(
                        self._cache, jnp.asarray(tail, jnp.int32),
                        jnp.asarray(priv, jnp.int32))
                    self.cow_copies += 1
                blocks.append(priv)
        blocks += self._alloc.alloc(
            self._alloc.blocks_for(total_rows) - len(blocks))
        self._slot_blocks[sid] = blocks
        self._slot_shared_rows[sid] = shared_rows
        row = jnp.asarray(
            blocks + [self._parking] * (self.max_blocks - len(blocks)),
            jnp.int32)
        self._cache = dict(self._cache, block_tables=self._table_fn(
            self._cache["block_tables"], row, jnp.asarray(sid, jnp.int32)))
        self.peak_used_blocks = max(self.peak_used_blocks,
                                    self._alloc.used_blocks)
        return blocks

    def _release_slot_blocks(self, sid: int) -> None:
        """Return slot ``sid``'s block references to the pool and park its
        table row (shared prefix blocks survive via their other holders)."""
        blocks = self._slot_blocks.pop(sid, None)
        if blocks is None:
            return
        self._alloc.release(blocks)
        self._slot_shared_rows.pop(sid, None)
        self._cache = dict(self._cache, block_tables=self._table_fn(
            self._cache["block_tables"], self._parking_row,
            jnp.asarray(sid, jnp.int32)))

    def _admit_paged(self, req: Request, sid: int):
        """Paged admission: map blocks (sharing any registered prefix),
        prefill only the unshared suffix, then register the prefix for
        later admissions.  Returns the last-token logits (1, V)."""
        plen = len(req.prompt)
        cap = min(req.prefix_len, plen - 1)   # >= 1 suffix token ALWAYS
        shared_rows, shared_blocks = 0, []
        if self._registry is not None and cap > 0:
            shared_rows, shared_blocks = self._registry.lookup(
                req.prompt, cap)
        blocks = self._map_slot_blocks(sid, plen + req.max_new_tokens,
                                       shared_rows, shared_blocks)
        if "mamba" in self._cache:
            self._cache = self._reset_fn(self._cache, sid)
        logits = self._prefill_rows(req.prompt[shared_rows:],
                                    sid, shared_rows)
        self.prefill_tokens_skipped += shared_rows
        if self._registry is not None and cap > 0:
            self._registry.register(req.prompt, cap,
                                    blocks[:self._alloc.blocks_for(cap)])
        return logits

    def capacity_hint(self, rows: int) -> int:
        """Admissions of ``rows``-row requests this engine could take
        right now: free slots under the occupancy limit AND — paged —
        block-pool headroom.  The fleet scheduler reads this instead of
        raw slot arithmetic so placement respects pool pressure."""
        room = self.slot_limit - self.active_slots
        if not self.paged:
            return max(0, room)
        per = max(1, -(-max(rows, 1) // self.block_size))
        if getattr(self, "_alloc", None) is None:      # stream not up yet
            return max(0, min(room, self.n_blocks // per))
        return max(0, min(room, self._alloc.free_blocks // per))

    # -- serving loop ------------------------------------------------------
    #
    # The loop is exposed incrementally — ``start`` installs a request
    # stream, each ``step`` runs one admission round plus one decode chunk
    # — so an external driver (the fleet scheduler in ``repro.fleet``) can
    # interleave serving work with other duties and preempt between chunks
    # without losing in-flight state.  ``generate`` is the classic
    # run-to-drain form on top.

    def _ensure_stream(self) -> None:
        """Bring up the device-resident stream state if none is active
        (fresh engine, or first restore after a drain)."""
        if getattr(self, "_sched", None) is not None:
            return
        self._t0 = time.perf_counter()
        self._sched = SlotScheduler(self.batch_size)
        self._sched.set_limit(self._slot_limit)
        B = self.batch_size
        if self.paged:
            # pool holds one PARKING block beyond the allocator's arena:
            # unmapped/released table entries point at it, never at an
            # allocatable block.  (Inside one scatter-kernel call a
            # retired lane still copies its mapped blocks through to the
            # aliased output; parking that lane on an unallocatable block
            # keeps the copy-through off blocks a later owner writes.)
            self._parking = self.n_blocks
            self._cache = lm.init_paged_cache(
                self.ctx, self.cfg, B, self.max_seq, self.block_size,
                n_blocks=self.n_blocks + 1)
            self._parking_row = jnp.full((self.max_blocks,), self._parking,
                                         jnp.int32)
            self._cache["block_tables"] = jnp.broadcast_to(
                self._parking_row, (B, self.max_blocks))
            self._alloc = BlockAllocator(self.n_blocks, self.block_size)
            self._registry = (PrefixRegistry(self._alloc)
                              if self.prefix_sharing else None)
            self._slot_blocks: dict[int, list[int]] = {}
            self._slot_shared_rows: dict[int, int] = {}
        else:
            self._cache = lm.init_cache(self.ctx, self.cfg, B, self.max_seq)
            self._alloc = self._registry = None
        self._cur = jnp.zeros((B,), jnp.int32)
        self._index = jnp.zeros((B,), jnp.int32)
        self._rem = jnp.zeros((B,), jnp.int32)
        self._done = jnp.ones((B,), bool)
        # ``finished`` is a ledger: it survives drain/restore cycles and
        # is only reset by ``start`` (a genuinely fresh stream)
        if not hasattr(self, "finished"):
            self.finished: list[Request] = []

    def _validate_requests(self, requests) -> None:
        """Reject unservable requests before any device work: rows beyond
        ``max_seq``, or (paged) a lifetime block reservation no empty pool
        could ever cover — which would deadlock the FCFS admission gate."""
        for req in requests:
            total = len(req.prompt) + req.max_new_tokens
            if total > self.max_seq:
                raise ValueError(
                    f"request {req.uid}: prompt {len(req.prompt)} + "
                    f"max_new_tokens {req.max_new_tokens} exceeds "
                    f"max_seq {self.max_seq}")
            if self.paged and -(-total // self.block_size) > self.n_blocks:
                raise ValueError(
                    f"request {req.uid}: needs "
                    f"{-(-total // self.block_size)} blocks but the pool "
                    f"holds {self.n_blocks}")

    def start(self, requests: list[Request]) -> None:
        """Install a FRESH request stream (any previous stream state is
        reset).  Steps are then driven by ``step()`` until ``pending`` is
        False.  To continue drained work instead, use ``restore``."""
        # validate up front: one oversize request must not abort the call
        # after other requests already burned device work
        self._validate_requests(requests)
        self._sched = None
        self._restore_q.clear()
        self.finished = []
        self._ensure_stream()
        self._sched.submit(requests)
        if self.tracer.enabled:
            for req in requests:
                self.tracer.instant("submit", self._vt, self.trace_track,
                                    cat="serving", args={"uid": req.uid})

    def submit(self, requests: list[Request]) -> None:
        """Queue MORE requests onto the stream without resetting it —
        the open-loop feed (``repro.workload`` offers arrivals while
        earlier requests are still decoding).  Brings the stream up if
        none is active; oversize requests are rejected up front, same
        as ``start``."""
        self._validate_requests(requests)
        self._ensure_stream()
        self._sched.submit(requests)
        if self.tracer.enabled:
            for req in requests:
                self.tracer.instant("submit", self._vt, self.trace_track,
                                    cat="serving", args={"uid": req.uid})

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (FCFS queue + snapshots not yet
        re-admitted) — the backpressure signal autoscaling reads."""
        sched = getattr(self, "_sched", None)
        q = len(self._restore_q)
        return q + (len(sched.queue) if sched is not None else 0)

    @property
    def active_slots(self) -> int:
        """Slots currently occupied by an in-flight request."""
        sched = getattr(self, "_sched", None)
        return len(sched.active()) if sched is not None else 0

    def _export_slots(self, sched, chosen) -> list[SlotSnapshot]:
        """Export ``chosen`` active slots as warm snapshots (two host
        syncs total: the cursor vectors, then every payload in one
        stacked transfer) and release them from the scheduler."""
        if not chosen:
            return []
        # sync 1: the cursor vectors (kv_len gates the payload slice)
        cur, index, rem = self._fetch(
            (self._cur, self._index, self._rem))
        # sync 2: every slot's payload in ONE stacked transfer (quantized
        # on device first when snapshot_int8 — half the bytes cross).
        # Paged slots ship only rows [shared, kv_len): the shared prefix
        # is rebuildable at the destination (registry hit or re-prefill),
        # so prefix sharing also shrinks migrations.
        payloads = self._fetch([self._export_payload(slot.sid,
                                                     int(index[slot.sid]))
                                for slot in chosen])
        self.sync_count += 2
        snaps = []
        for slot, payload in zip(list(chosen), payloads):
            sid = slot.sid
            snaps.append(SlotSnapshot(
                request=slot.request, rem=int(rem[sid]),
                kv_len=int(index[sid]), cur=int(cur[sid]), payload=payload,
                prefix_len=(self._slot_shared_rows.get(sid, 0)
                            if self.paged else 0)))
            sched.release(slot)
            if self.paged:
                self._release_slot_blocks(sid)
        return snaps

    def _export_payload(self, sid: int, kv_len: int):
        """One slot's (device-side) snapshot payload — dense or paged;
        identical schema either way, so payloads are layout-portable."""
        if not self.paged:
            return lm.export_slot(self.cfg, self._cache, sid, kv_len,
                                  quantize=self.snapshot_int8)
        return lm.export_slot_paged(
            self.cfg, self._cache, sid, self._slot_blocks[sid],
            self.block_size, kv_len,
            row_start=self._slot_shared_rows.get(sid, 0),
            quantize=self.snapshot_int8)

    def select_victims(self, n: int) -> list[int]:
        """Slot ids of the ``n`` partial-drain victims the engine's
        ``victim_policy`` picks (default: fewest remaining tokens first)
        — the ``slots=`` argument a proportional ``drain`` wants."""
        sched = getattr(self, "_sched", None)
        if sched is None or n <= 0:
            return []
        return [s.sid for s in self.victim_policy(sched.active())[:n]]

    def set_slot_limit(self, limit: int) -> None:
        """Cap concurrent occupancy below ``batch_size`` (a partial
        preemption sheds capacity, not just current occupants: freed
        lanes must not refill from the queue until the cap is raised).
        The cap survives drain/restore cycles."""
        if not 1 <= limit <= self.batch_size:
            raise ValueError(f"slot limit must be in [1, "
                             f"{self.batch_size}], got {limit}")
        self._slot_limit = limit
        sched = getattr(self, "_sched", None)
        if sched is not None:
            sched.set_limit(limit)

    @property
    def slot_limit(self) -> int:
        return self._slot_limit

    def drain(self, slots=None) -> list[SlotSnapshot]:
        """Stop the stream LOSSLESSLY — entirely, or slot by slot.

        ``slots=None`` (full drain): every in-flight slot is exported as
        a warm ``SlotSnapshot`` (cache lane + decode cursor), every
        queued / not-yet-installed request as a cold one.  The engine is
        left idle (``pending`` is False) and the snapshots can be
        ``restore``d here or on any engine with the same model config —
        preemption becomes a drain, not a discard.

        ``slots=[sid, ...]`` (partial drain): ONLY the named slots are
        exported and their decode lanes masked; every surviving slot
        keeps decoding bit-identically to an unpreempted run (per-slot
        cache state is independent — the same property that makes
        continuous batching match solo decoding).  The stream stays up;
        pair with ``set_slot_limit`` to keep the shed lanes empty."""
        sched = getattr(self, "_sched", None)
        if sched is None:
            return []
        if slots is not None:
            want = set(slots)
            chosen = [s for s in sched.active() if s.sid in want]
            snaps = self._export_slots(sched, chosen)
            if snaps:
                # mask the drained lanes: done slots write at max_seq
                # (dropped) and emit nothing — survivors are untouched
                sids = jnp.asarray([s.sid for s in chosen], jnp.int32)
                self._done = self._done.at[sids].set(True)
                self._rem = self._rem.at[sids].set(0)
                self._cur = self._cur.at[sids].set(0)
            return snaps
        snaps = self._export_slots(sched, sched.active())
        snaps.extend(self._restore_q)
        self._restore_q.clear()
        snaps.extend(SlotSnapshot(request=req,
                                  rem=req.max_new_tokens)
                     for req in sched.queue)
        self._sched = None          # stream torn down; cache freed
        self._cache = None
        self._alloc = self._registry = None   # pool (and cached prefixes) die
        return snaps

    def checkpoint(self) -> list[SlotSnapshot]:
        """Shadow-checkpoint the WHOLE stream non-destructively: every
        in-flight slot is exported as a warm ``SlotSnapshot`` (same two
        stacked host syncs as a drain), every awaiting-restore or queued
        request as its current snapshot/cold form — but nothing is
        released and decoding continues untouched.  Requests are CLONED
        into the snapshots, so later decode on the live stream cannot
        mutate the checkpoint: ``restore``-ing it (typically on another
        node, after a crash) replays from exactly this boundary,
        bit-identically under greedy decoding."""
        sched = getattr(self, "_sched", None)
        if sched is None:
            return []
        snaps: list[SlotSnapshot] = []
        active = sched.active()
        if active:
            cur, index, rem = self._fetch(
                (self._cur, self._index, self._rem))
            payloads = self._fetch([self._export_payload(slot.sid,
                                                         int(index[slot.sid]))
                                    for slot in active])
            self.sync_count += 2
            for slot, payload in zip(active, payloads):
                sid = slot.sid
                snaps.append(SlotSnapshot(
                    request=slot.request.clone(), rem=int(rem[sid]),
                    kv_len=int(index[sid]), cur=int(cur[sid]),
                    payload=payload,
                    prefix_len=(self._slot_shared_rows.get(sid, 0)
                                if self.paged else 0)))
        for s in self._restore_q:
            snaps.append(SlotSnapshot(
                request=s.request.clone(), rem=s.rem, kv_len=s.kv_len,
                cur=s.cur, payload=s.payload, prefix_len=s.prefix_len))
        snaps.extend(SlotSnapshot(request=req.clone(),
                                  rem=req.max_new_tokens)
                     for req in sched.queue)
        return snaps

    def abandon(self) -> None:
        """Crash path: tear the stream down WITHOUT exporting anything —
        the device is gone, there is nothing to drain.  In-flight work
        not covered by an earlier ``checkpoint`` is lost; the engine is
        left idle and can be restarted with ``start``/``restore``."""
        self._sched = None
        self._cache = None
        self._alloc = self._registry = None
        self._restore_q.clear()

    def restore(self, snaps: list[SlotSnapshot]) -> None:
        """Admit drained snapshots into this engine's stream (started on
        demand).  Warm snapshots re-install their cache lane and resume
        their cursor the moment a slot frees — ahead of fresh
        admissions; cold ones join the ordinary FCFS queue.  Requests
        continue BIT-IDENTICALLY to an uninterrupted run."""
        for s in snaps:
            need = s.kv_len + s.rem if s.warm \
                else len(s.request.prompt) + s.request.max_new_tokens
            if need > self.max_seq:
                raise ValueError(
                    f"request {s.request.uid}: snapshot needs {need} cache "
                    f"rows but this engine holds max_seq {self.max_seq}")
            if self.paged and -(-need // self.block_size) > self.n_blocks:
                raise ValueError(
                    f"request {s.request.uid}: snapshot needs "
                    f"{-(-need // self.block_size)} blocks but the pool "
                    f"holds {self.n_blocks}")
        self._ensure_stream()
        tr = self.tracer if self.tracer.enabled else None
        for s in snaps:
            if not s.warm:
                self._sched.submit([s.request])
                if tr is not None:
                    tr.instant("submit", self._vt, self.trace_track,
                               cat="serving", args={"uid": s.request.uid})
            elif s.rem <= 0:        # finished between export and restore
                self.finished.append(s.request)
            else:
                self._restore_q.append(s)
                if tr is not None:
                    tr.instant("restore", self._vt, self.trace_track,
                               cat="serving",
                               args={"uid": s.request.uid,
                                     "bytes": s.payload_bytes,
                                     "kv_len": s.kv_len})

    def _install_snapshot(self, snap: SlotSnapshot, sid: int) -> None:
        """Write a warm snapshot's cache lane into slot ``sid`` and arm
        its decode lane at the restored cursor.  A ``prefix_len > 0``
        payload is prefix-trimmed: rows [0, prefix_len) are rebuilt here —
        from this engine's prefix registry when the tokens are cached
        (nothing recomputed), else by re-prefilling that prompt span."""
        payload = jax.tree.map(jnp.asarray, snap.payload)
        prompt, pfx = snap.request.prompt, snap.prefix_len
        if self.paged:
            shared_rows, shared_blocks = 0, []
            if self._registry is not None and pfx > 0:
                shared_rows, shared_blocks = self._registry.lookup(
                    prompt, pfx)
            blocks = self._map_slot_blocks(sid, snap.kv_len + snap.rem,
                                           shared_rows, shared_blocks)
            if "mamba" in self._cache:
                self._cache = self._reset_fn(self._cache, sid)
            if shared_rows < pfx:
                n = len(chunk_plan(pfx - shared_rows, self.prefill_chunk))
                with self._phase("prefill", calls=n):
                    self._prefill_rows(prompt[shared_rows:pfx],
                                       sid, shared_rows)
            self.prefill_tokens_skipped += shared_rows
            self._cache = lm.import_slot_paged(
                self.cfg, self._cache, payload, sid, blocks,
                self.block_size, row_offset=pfx, mode=self.run.kernel_mode)
            if self._registry is not None and pfx > 0:
                self._registry.register(
                    prompt, pfx, blocks[:self._alloc.blocks_for(pfx)])
        else:
            # the dense importer overwrites the WHOLE lane (rows below
            # row_offset are zeroed), so the prefix re-prefill must come
            # AFTER the import, not before
            self._cache = lm.import_slot(self.cfg, self._cache, payload,
                                         sid, mode=self.run.kernel_mode,
                                         row_offset=pfx)
            if pfx > 0:
                n = len(chunk_plan(pfx, self.prefill_chunk))
                with self._phase("prefill", calls=n):
                    self._prefill_rows(prompt[:pfx], sid, 0)
        self._cur, self._index, self._rem, self._done = self._install_fn(
            self._cur, self._index, self._rem, self._done,
            jnp.asarray(snap.cur, jnp.int32), sid, snap.kv_len, snap.rem)

    @property
    def pending(self) -> bool:
        """Whether the installed stream still has queued, restorable or
        in-flight requests (False before ``start``/``restore`` and after
        ``drain``)."""
        if self._restore_q:
            return True
        sched = getattr(self, "_sched", None)
        return sched.has_work if sched is not None else False

    @property
    def in_flight_tokens(self) -> int:
        """Tokens already generated for requests still occupying slots
        (delivered to the Request but not yet finished) — what an
        external driver loses if it abandons the stream mid-stint."""
        sched = getattr(self, "_sched", None)
        if sched is None:
            return 0
        return sum(len(s.request.generated) for s in sched.active())

    def step(self) -> list[Request]:
        """One engine step: admit whatever fits the free slots (restored
        snapshots first, then fresh prefills), run one decode chunk,
        deliver the chunk's tokens.  Returns the requests that finished
        THIS step (also appended to ``self.finished``)."""
        if not self.pending:
            return []
        sched = self._sched
        tr = self.tracer if self.tracer.enabled else None
        chunk_t0 = self._vt
        # restored slots first: their work is already paid for — a warm
        # snapshot install is a cache write, not a prefill program
        while self._restore_q:
            snap = self._restore_q[0]
            if self.paged and not self._fits_blocks(
                    snap.request.prompt, snap.kv_len + snap.rem,
                    snap.prefix_len):
                break               # FCFS: later snapshots wait too
            slot = sched.occupy(snap.request)
            if slot is None:
                break
            self._install_snapshot(self._restore_q.popleft(), slot.sid)
        # one phase entry per admitted request = one prefill program
        # run under the prefill cap (back-to-back entries coalesce the
        # cap write; the modeled measurement accounts each prefill)
        can_admit = self._can_admit if self.paged else None
        for slot in sched.admit_ready(can_admit=can_admit):
            req = slot.request
            plen = len(req.prompt)
            # phase cost in CHUNK PROGRAMS actually run: a shared prefix
            # skips its chunks, a long prompt costs more than a short one
            skip = self._shared_credit(
                req.prompt, min(req.prefix_len, plen - 1)) if self.paged \
                else 0
            n_calls = len(chunk_plan(plen - skip, self.prefill_chunk))
            with self._phase("prefill", calls=n_calls) as rec:
                if self.paged:
                    logits = self._admit_paged(req, slot.sid)
                else:
                    self._cache, logits = self._prefill_into_slot(
                        self._cache, req, slot.sid)
            self._cur, self._index, self._rem, self._done = self._admit_fn(
                self._cur, self._index, self._rem, self._done, logits,
                slot.sid, len(slot.request.prompt),
                slot.request.max_new_tokens)
            if tr is not None:
                m = getattr(rec, "modeled", None)
                dt = m.runtime if m is not None else 1.0
                tr.span("prefill", self._vt, self._vt + dt,
                        self.trace_track, cat="phase",
                        args={"uid": slot.request.uid,
                              "energy_j": m.energy if m is not None
                              else 0.0})
                self._vt += dt
        uids = [s.request.uid for s in sched.active()] \
            if tr is not None else None
        with self._phase("decode", calls=self.decode_chunk) as rec:
            (self._cache, self._cur, self._index, self._rem, self._done,
             out, _) = self._decode_fn(
                self.params, self._cache, self._cur, self._index,
                self._rem, self._done)
        if tr is not None:
            m = getattr(rec, "modeled", None)
            dt = m.runtime if m is not None else 1.0
            tr.span("decode", self._vt, self._vt + dt, self.trace_track,
                    cat="phase",
                    args={"uids": uids,
                          "energy_j": m.energy if m is not None else 0.0})
            self._vt += dt
            tr.span("engine.chunk", chunk_t0, self._vt, self.trace_track,
                    cat="chunk", args={"active": len(uids)})
        out_host = self._fetch(out)           # the chunk's ONE sync
        self.sync_count += 1
        now = time.perf_counter() - self._t0
        newly: list[Request] = []
        for slot in sched.active():
            row = out_host[slot.sid]
            fresh = [int(t) for t in row[:_valid_len(row)]]
            slot.request.generated.extend(fresh)
            slot.emitted += len(fresh)
            if slot.emitted >= slot.request.max_new_tokens:
                self.completion_s[slot.request.uid] = now
                newly.append(sched.release(slot))
                if self.paged:
                    self._release_slot_blocks(slot.sid)
        self.finished.extend(newly)
        return newly

    def generate(self, requests: list[Request]) -> list[Request]:
        self.start(requests)
        while self.pending:
            self.step()
        return self.finished


def _valid_len(row) -> int:
    """Emitted tokens are a -1-terminated prefix of the chunk buffer."""
    n = 0
    for t in row:
        if t < 0:
            break
        n += 1
    return n
