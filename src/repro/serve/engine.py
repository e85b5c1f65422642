"""Serving engines.

:class:`Engine` — the continuous-batching step-loop engine over a paged KV
cache (serve/cache.py + serve/scheduler.py):

  * ``submit(prompt, max_new_tokens=…, temperature=…, seed=…,
    stop_tokens=…, deadline_steps=…) -> rid`` — enqueue a request
    (per-request sampling params, stop conditions, and an optional TTL on
    the scheduler clock).  Admission control may *shed* the request: the
    returned rid's request is then terminal ``REJECTED`` with a structured
    reason, never having touched the block pool;
  * ``step() -> {rid: [new tokens]}`` — one engine step: admit waiting
    requests into free batch slots (sharing prefix-cache blocks when
    their prompt prefix is already pooled), run each mid-prefill
    request's next *chunk* (``model.prefill_chunk`` writes straight into
    pool blocks — no dense intermediate), then ONE jitted decode step
    over the decode-ready slots — per-request ``(B,)`` positions,
    block-table gather attention, in-step sampling.  Chunked prefill
    (Sarathi-style, ``prefill_chunk_tokens``) bounds per-step latency
    and kills head-of-line blocking; ``prefill_chunk_tokens=0`` prefills
    whole prompts in one chunk;
  * ``stream(rid)`` / ``run()`` — drive ``step`` until a request / all
    requests reach a terminal state.

Robustness machinery (see serve/faults.py and the chaos suite):

  * **fault injection** — ``Engine(faults=FaultInjector(...))`` threads a
    deterministic, seeded fault schedule through the step loop: pool
    squeezes, NaN-poisoned logits, dropped/slow decode steps, corrupted
    pool blocks, preemption storms — all replayable byte-for-byte;
  * **NaN/Inf quarantine** — the decode step returns a per-row finite
    flag; a poisoned row is terminally ``FAILED`` (its exclusive blocks
    scrubbed then freed, shared refcounts intact) while the rest of the
    batch streams on — batch invariance means the survivors' tokens are
    unchanged;
  * **retry with capped backoff** — a dropped decode step advances no
    request; the engine backs off exponentially (capped) and retries,
    failing a request only after ``max_retries`` dropped attempts;
  * **forward-progress watchdog** — repeated preempt/readmit with no
    emitted tokens degrades admission to serial until pressure clears
    (scheduler-side; see Scheduler.record_progress);
  * **invariant auditing** — ``Engine(audit=True)`` re-checks allocator
    conservation, prefix-trie integrity, and block-table ownership after
    every step, raising a structured :class:`AuditFailure` naming the
    violated invariant.

Determinism: sampling keys are ``fold_in(PRNGKey(seed), position)`` — a
request's token stream depends only on its own (prompt, params), never on
what else is in the batch, which is the batch-invariance property the test
suite asserts.  Preemption (pool pressure) is recompute-style: the
victim's blocks are freed and its context is re-prefilled on re-admission,
so no emitted token is lost or re-sampled.  Faults perturb *scheduling*,
never a surviving request's numerics — fault-free requests stream
token-identical to a zero-fault run.

:class:`FixedSlotEngine` — the seed engine's fixed-slot ``generate`` API
(one prefill + a dense contiguous cache), upgraded to per-request
positions and a capacity-padded cache (the seed version silently
ring-overwrote the oldest prompt tokens once ``pos`` wrapped).  It is the
dense-cache oracle the paged engine is differentially tested against.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.cache import PagedKVCache
from repro.serve.faults import FAULT_OWNER, FaultInjector
from repro.serve.scheduler import (DECODE, PREFILL, Request, SamplingParams,
                                   Scheduler)
from repro.serve.speculative import (AdaptiveDepth, DraftSource, SpecConfig,
                                     make_draft)

# dense-cache keys whose seq axis (2) gets decode headroom padding.
# ssm/hybrid are absent: their prefill builds no decode cache (seed
# behavior), so neither engine can serve them.
_PAD_KEYS = ("k", "v", "ckv")


def _sample(logits, temps, keys):
    """Per-request sampling: greedy at temperature 0, else categorical
    under the request's own key. logits (B, V) f32; temps (B,); keys
    (B,) PRNG keys (uint32 (B, 2) key data)."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    drawn = jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, scaled)
    return jnp.where(temps > 0, drawn, greedy).astype(jnp.int32)


class Engine:
    """Paged continuous-batching serving engine (see module docstring)."""

    def __init__(self, model, params, *, max_batch: int = 8,
                 block_size: Optional[int] = None, n_blocks: int = 128,
                 max_blocks_per_req: Optional[int] = None,
                 use_mesh_sharding: bool = True,
                 prefill_chunk_tokens: int = 32,
                 prefix_cache: bool = True,
                 max_queue: Optional[int] = None,
                 admit_watermark: float = 0.0,
                 max_retries: int = 8,
                 backoff_cap: int = 8,
                 watchdog_window: int = 8,
                 watchdog_threshold: int = 3,
                 audit: bool = False,
                 faults: Optional[FaultInjector] = None,
                 spec: Optional[SpecConfig] = None,
                 draft: Optional[DraftSource] = None):
        cfg = model.cfg
        if cfg.arch_type not in ("dense", "moe"):
            raise ValueError(
                f"the paged engine serves dense/moe decoders "
                f"(got {cfg.arch_type!r}); use FixedSlotEngine")
        if model.rt.par.batch_axes:
            # serving shapes are ragged (B=1 prefills, a fixed slot batch
            # for decode): run the model batch-replicated — the sequence
            # axis keeps its sharding
            from repro.models.transformer import build_model
            model = build_model(cfg, dataclasses.replace(
                model.rt, par=dataclasses.replace(model.rt.par,
                                                  batch_axes=())))
        self.model = model
        self.params = params
        self.cfg = cfg
        mesh = model.rt.mesh if use_mesh_sharding else None
        self.cache = PagedKVCache.create(
            cfg, block_size=block_size, n_blocks=n_blocks,
            max_reqs=max_batch, max_blocks_per_req=max_blocks_per_req,
            mesh=mesh, seq_axis=model.rt.par.seq_axis,
            prefix_cache=prefix_cache)
        # speculative decoding: the scheduler reserves the draft write
        # span (lookahead), the engine swaps its one-token decode for the
        # multi-token verify step (see serve/speculative.py)
        self.spec = spec
        self.draft = (draft if draft is not None
                      else make_draft(spec) if spec is not None else None)
        self._adepth = (AdaptiveDepth(spec)
                        if spec is not None and spec.adaptive else None)
        # {effective draft budget: spec-step row count} — how deep the
        # controller actually lets each request draft
        self.spec_depth_hist: Dict[int, int] = {}
        self.sched = Scheduler(self.cache, max_batch,
                               prefill_chunk_tokens=prefill_chunk_tokens,
                               max_queue=max_queue,
                               admit_watermark=admit_watermark,
                               watchdog_window=watchdog_window,
                               watchdog_threshold=watchdog_threshold,
                               lookahead=spec.depth if spec else 0)
        self.max_batch = max_batch
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.requests: Dict[int, Request] = {}
        # chunk lengths are padded up to a bucket — the fixed chunk size,
        # or (whole-prompt mode) a multiple of the block size and the
        # sequence-shard count — so the number of prefill compilations is
        # bounded by the number of buckets, not by the number of distinct
        # prompt/requeue lengths.  Chunk logits are never computed (the
        # last context token enters via decode), and padded rows write to
        # the null block, so tail padding is free
        self._prefill_bucket = math.lcm(self.cache.block_size,
                                        max(self.model.rt.seq_size, 1))
        # the block pools are donated: every step's scatters update them
        # in place instead of copying the whole pool every token
        self._chunk_jit = jax.jit(self._chunk_step_fn, donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode_step_fn, donate_argnums=(1,))
        self._verify_jit = jax.jit(self._verify_step_fn, donate_argnums=(1,))
        self._base_keys: Dict[int, jax.Array] = {}
        # robustness state
        self.audit_mode = bool(audit)
        self.max_retries = int(max_retries)
        self.backoff_cap = int(backoff_cap)
        self.injector = faults
        self.step_idx = 0                 # fault-schedule timeline
        self._squeezes: List[Tuple[int, List[int]]] = []  # (release, ids)
        self._backoff_until = 0
        self._consec_drops = 0
        self.counters = dict(quarantined=0, retried=0, backoff_steps=0,
                             audit_passes=0, spec_proposed=0,
                             spec_accepted=0, spec_rejected=0,
                             spec_rollbacks=0)

    def install_faults(self, injector: Optional[FaultInjector]) -> None:
        """(Re-)attach a fault schedule with its timeline starting at the
        *next* step — lets benches warm up fault-free, then storm."""
        self.release_faults()
        self.injector = injector
        self.step_idx = 0

    # -------------------------------------------------------------- intake
    def submit(self, prompt, *, max_new_tokens: int = 16,
               temperature: float = 0.0, seed: int = 0,
               stop_tokens: Tuple[int, ...] = (),
               deadline_steps: Optional[int] = None) -> int:
        params = SamplingParams(max_new_tokens=max_new_tokens,
                                temperature=float(temperature),
                                seed=int(seed),
                                stop_tokens=tuple(int(t)
                                                  for t in stop_tokens))
        req = self.sched.submit(prompt, params,
                                deadline_steps=deadline_steps)
        self.requests[req.rid] = req
        if not req.done:                  # shed requests never run
            self._base_keys[req.rid] = jax.random.PRNGKey(params.seed)
        return req.rid

    def status(self, rid: int) -> Tuple[str, Optional[str]]:
        """(state, finish_reason) of a request — terminal states are
        definite: finished / rejected / expired / failed."""
        req = self.requests[rid]
        return req.state, req.finish_reason

    # ------------------------------------------------------------- prefill
    _NKV_BUCKET = 4          # table-width shape bucket for the chunk jit

    def _chunk_pad(self, n: int) -> int:
        """Padded chunk length: the fixed chunk size, or (whole-prompt
        mode) ``n`` rounded up to the prefill bucket."""
        if self.prefill_chunk_tokens:
            return self.prefill_chunk_tokens
        b = self._prefill_bucket
        return max(b, -(-n // b) * b)

    def _nkv_for(self, end: int) -> int:
        """Block-table width shipped to the chunk jit: covers the chunk's
        last written position, bucketed to bound recompilation.  Depends
        only on ``end`` (absolute context position), so a request's chunk
        shapes never depend on batch composition or cache hits."""
        need = -(-end // self.cache.block_size)
        return min(self.cache.max_blocks_per_req,
                   -(-need // self._NKV_BUCKET) * self._NKV_BUCKET)

    def _chunk_step_fn(self, params, pools, bt, start, n_valid, tokens):
        out = self.model.prefill_chunk(
            params, {**pools, "block_table": bt},
            {"tokens": tokens, "start": start, "n_valid": n_valid})
        return {k: out[k] for k in pools}

    def _run_chunk(self, req: Request, start: int, n: int) -> None:
        """Run one prefill chunk: context positions [start, start+n) of
        ``req`` are forwarded and their KV scattered into the slot's
        blocks (the scheduler already forked any shared block the chunk
        writes)."""
        end = start + n
        C = self._chunk_pad(n)
        toks = np.zeros((C,), np.int32)
        toks[:n] = req.context[start:end]
        nkv = self._nkv_for(end)
        # a private copy: the transfer may read host memory after this
        # returns, and the scheduler edits the live table meanwhile
        bt = jnp.asarray(self.cache.table[req.slot:req.slot + 1, :nkv].copy())
        self.cache.pools = self._chunk_jit(
            self.params, self.cache.pools, bt, jnp.int32(start),
            jnp.int32(n), jnp.asarray(toks)[None])

    def warm_prefill(self, max_ctx: int) -> int:
        """Pre-compile every (chunk length, table width) shape a trace of
        up to ``max_ctx`` context tokens can reach, by running dummy
        chunks against an all-null block table (writes land in the
        reserved null block; no allocator state is touched).  Returns the
        number of shapes compiled — bench warmup aid."""
        shapes = {(self._chunk_pad(min(e, self.prefill_chunk_tokens or e)),
                   self._nkv_for(e)) for e in range(1, max_ctx + 1)}
        for C, nkv in sorted(shapes):
            self.cache.pools = self._chunk_jit(
                self.params, self.cache.pools,
                jnp.zeros((1, nkv), jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.zeros((1, C), jnp.int32))
        return len(shapes)

    # -------------------------------------------------------------- decode
    def _decode_step_fn(self, params, pools, table, pos, tok, temps, keys,
                        poison):
        cache = {**pools, "block_table": table}
        logits, cache2 = self.model.decode(params, cache,
                                           {"token": tok, "pos": pos})
        # poison is all-zero in normal operation (adding 0 is exact in
        # f32, so the fault hook costs nothing numerically); the NaN
        # guard's per-row finite flag is computed AFTER it so injected
        # and organic non-finites take the same quarantine path
        lf = logits[:, -1].astype(jnp.float32) + poison[:, None]
        ok = jnp.all(jnp.isfinite(lf), axis=-1)
        nxt = _sample(lf, temps, keys)
        return nxt, ok, {k: cache2[k] for k in pools}

    def _verify_step_fn(self, params, pools, table, pos, toks, n_write,
                        temps, base_keys, poison):
        """Speculative verify: score T = 1 + depth rows per request in one
        forward (row 0 = pending token, rows 1.. = draft proposals) and
        sample the target token for EVERY row under its own per-position
        key — the same ``fold_in(seed_key, position)`` keys the vanilla
        decode step uses, so the accept/reject walk on the host commits
        exactly the tokens the non-speculative engine would have."""
        cache = {**pools, "block_table": table}
        logits, cache2 = self.model.verify(
            params, cache, {"tokens": toks, "pos": pos, "n_write": n_write})
        lf = logits.astype(jnp.float32) + poison[:, None, None]
        ok = jnp.all(jnp.isfinite(lf), axis=-1)             # (B, T)
        offs = jnp.arange(toks.shape[1], dtype=jnp.int32)
        keys = jax.vmap(lambda k, p: jax.vmap(
            lambda t: jax.random.fold_in(k, p + 1 + t))(offs))(
                base_keys, pos)                             # (B, T) keys
        tgt = jax.vmap(_sample, in_axes=(1, None, 1), out_axes=1)(
            lf, temps, keys)                                # (B, T)
        return tgt, ok, {k: cache2[k] for k in pools}

    def _key_for(self, req: Request, position: int) -> jax.Array:
        """Sampling key of the token that will sit at context
        ``position`` — a pure function of (seed, position), so streams are
        batch- and preemption-invariant."""
        return jax.random.fold_in(self._base_keys[req.rid], position)

    # ------------------------------------------------------- fault plumbing
    def _release_due_squeezes(self) -> None:
        keep = []
        for release_step, ids in self._squeezes:
            if self.step_idx >= release_step:
                self.cache.allocator.free(ids, FAULT_OWNER)
            else:
                keep.append((release_step, ids))
        self._squeezes = keep

    def release_faults(self) -> None:
        """Return every fault-held (squeezed) block to the pool — called
        automatically when ``run`` drains; manual steppers may call it
        before checking conservation-at-exit."""
        for _, ids in self._squeezes:
            self.cache.allocator.free(ids, FAULT_OWNER)
        self._squeezes = []

    def _apply_pre_plan_faults(self, events) -> Tuple[bool, list]:
        """Apply squeeze / storm / corrupt / slow faults (they act on
        scheduler/cache state the upcoming plan must see).  Returns
        (decode_dropped, nan_events)."""
        inj, drop, nan_events = self.injector, False, []
        for e in events:
            if e.kind == "squeeze":
                take = min(e.magnitude, self.cache.allocator.n_free)
                if take:
                    ids = self.cache.allocator.alloc(FAULT_OWNER, take)
                    self._squeezes.append((self.step_idx + e.duration, ids))
                    inj.fired(self.step_idx, e.kind,
                              f"held {take} blocks for {e.duration} steps")
                else:
                    inj.fired(self.step_idx, e.kind, "no free blocks")
            elif e.kind == "preempt_storm":
                victims = self.sched.force_preempt(e.magnitude)
                inj.fired(self.step_idx, e.kind,
                          f"preempted rids {[v.rid for v in victims]}")
            elif e.kind == "slow_step":
                self.sched.advance_clock(e.magnitude)
                inj.fired(self.step_idx, e.kind,
                          f"+{e.magnitude} clock ticks")
            elif e.kind == "corrupt_block":
                victim, block = self._corruption_victim(e)
                if victim is None:
                    inj.fired(self.step_idx, e.kind, "no candidate")
                else:
                    self.cache.corrupt_block(block)
                    inj.fired(self.step_idx, e.kind,
                              f"rid={victim.rid} block={block}")
            elif e.kind == "drop_step":
                drop = True
                inj.fired(self.step_idx, e.kind, "decode step dropped")
            elif e.kind == "nan_logits":
                nan_events.append(e)      # resolved once live rows known
        return drop, nan_events

    def _corruption_victim(self, event):
        """Deterministic corruption target: a decode-phase request's last
        block, exclusively owned (never a shared/prefix-indexed block —
        corruption must poison exactly one request)."""
        cands = []
        for slot in sorted(self.sched.running):
            r = self.sched.running[slot]
            if r.cached < r.n_prefill:
                continue
            n = int(self.cache.n_assigned[slot])
            b = int(self.cache.table[slot, n - 1]) if n else 0
            if b and self.cache.allocator.owners(b) == (r.rid,):
                cands.append((r, b))
        pick = self.injector.pick(event, cands)
        return pick if pick is not None else (None, None)

    def _quarantine(self, req: Request, reason: str) -> None:
        """Terminally fail one poisoned request: scrub its exclusively
        owned blocks (NaN content must not survive into the free list),
        release its refs (shared blocks stay intact under their other
        owners), and keep its clean partial stream."""
        self.cache.scrub_slot(req.slot, req.rid)
        self.sched.fail(req, reason)
        self.counters["quarantined"] += 1

    def _release_draft(self, rid: int) -> None:
        """Terminal-state hook: drop draft-model state AND the adaptive
        depth controller's acceptance history for this request (rids are
        never reused, but the dicts must not grow unboundedly)."""
        if self.draft is not None:
            self.draft.release(rid)
        if self._adepth is not None:
            self._adepth.release(rid)

    # ---------------------------------------------------------- the loop
    def _emit(self, req: Request, token: int, events) -> None:
        req.emitted.append(int(token))
        events.setdefault(req.rid, []).append(int(token))
        if token in req.params.stop_tokens:
            self.sched.finish(req, "stop")
        elif len(req.emitted) >= req.params.max_new_tokens:
            self.sched.finish(req, "length")

    def step(self) -> Dict[int, List[int]]:
        """One engine step. Returns {rid: [tokens emitted this step]}."""
        self._release_due_squeezes()
        drop, nan_events = False, []
        if self.injector is not None:
            drop, nan_events = self._apply_pre_plan_faults(
                self.injector.events_for(self.step_idx))

        plan = self.sched.plan()
        events: Dict[int, List[int]] = {}
        for r in plan.expired:
            self._release_draft(r.rid)

        for req, start, n in plan.chunks:
            if req.state != PREFILL:       # preempted after planning
                continue
            self._run_chunk(req, start, n)
            req.cached = start + n
            if req.cached >= req.n_prefill:
                req.state = DECODE
            # index the newly completed full blocks so later arrivals
            # (and this request's own re-admissions) can share them
            self.cache.register_prefix(req.slot, req.rid, req.context,
                                       req.cached)

        live = [r for r in plan.decode if r.state == DECODE]
        n_tokens = 0
        if nan_events and (not live or drop
                           or self.step_idx < self._backoff_until):
            for e in nan_events:
                self.injector.fired(self.step_idx, e.kind,
                                    "no live decode row")
            nan_events = []
        if live and (drop or self.step_idx < self._backoff_until):
            # transient step fault (or backoff window): no request
            # advances — next attempt re-samples the same positions, so
            # streams are unchanged.  Capped exponential backoff between
            # attempts; a request fails only after max_retries drops.
            if drop:
                self._consec_drops += 1
                self._backoff_until = self.step_idx + 1 + min(
                    2 ** (self._consec_drops - 1), self.backoff_cap)
                for r in live:
                    r.retries += 1
                    self.counters["retried"] += 1
                    if r.retries > self.max_retries:
                        self.sched.fail(r, "retries_exhausted")
                        self._release_draft(r.rid)
            else:
                self.counters["backoff_steps"] += 1
        elif live and self.spec is not None:
            n_tokens = self._spec_step(live, nan_events, events)
        elif live:
            B = self.max_batch
            tok = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            keys = [jax.random.PRNGKey(0)] * B
            poison = np.zeros((B,), np.float32)
            for e in nan_events:
                victim = self.injector.pick(
                    e, sorted(live, key=lambda r: r.rid))
                poison[victim.slot] = np.nan
                self.injector.fired(self.step_idx, e.kind,
                                    f"rid={victim.rid}")
            # non-live rows (idle slots AND mid-prefill requests) still flow
            # through the decode step with pos=0/tok=0 — and decode *writes*
            # KV at pos through the table.  Ship them an all-null table row
            # so those writes land in the reserved null block instead of a
            # mid-prefill request's (possibly cache-shared) block 0
            tbl = np.zeros_like(self.cache.table)
            for r in live:
                tok[r.slot, 0] = r.pending
                pos[r.slot] = r.cached
                temps[r.slot] = r.params.temperature
                keys[r.slot] = self._key_for(r, r.cached + 1)
                tbl[r.slot] = self.cache.table[r.slot]
            nxt, ok, pools = self._decode_jit(
                self.params, self.cache.pools, jnp.asarray(tbl),
                jnp.asarray(pos), jnp.asarray(tok), jnp.asarray(temps),
                jnp.stack(keys), jnp.asarray(poison))
            self.cache.pools = pools
            nxt, ok = np.asarray(nxt), np.asarray(ok)
            self._consec_drops = 0
            for r in live:
                if not ok[r.slot]:
                    # NaN/Inf logits: quarantine exactly this row; the
                    # poisoned sample is discarded, the clean prefix of
                    # its stream is kept, and everyone else streams on
                    self._quarantine(r, "nan_logits")
                    continue
                r.retries = 0
                r.cached += 1
                self._emit(r, int(nxt[r.slot]), events)
                n_tokens += 1

        self.sched.record_progress(n_tokens)
        self.step_idx += 1
        if self.audit_mode:
            self.cache.audit(self.sched.running)
            self.counters["audit_passes"] += 1
        return events

    def _spec_step(self, live, nan_events, events) -> int:
        """One speculative decode step over the live rows: draft, verify,
        accept/reject walk.  Shapes are fixed at T = 1 + depth (short
        proposal lists are padded; ``n_write`` null-redirects the padding
        rows' KV writes and the walk never reads their samples), so the
        verify jit compiles once."""
        B, T = self.max_batch, 1 + self.spec.depth
        toks = np.zeros((B, T), np.int32)
        pos = np.zeros((B,), np.int32)
        n_write = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        bkeys = [jax.random.PRNGKey(0)] * B
        poison = np.zeros((B,), np.float32)
        for e in nan_events:
            victim = self.injector.pick(
                e, sorted(live, key=lambda r: r.rid))
            poison[victim.slot] = np.nan
            self.injector.fired(self.step_idx, e.kind, f"rid={victim.rid}")
        tbl = np.zeros_like(self.cache.table)
        props: Dict[int, List[int]] = {}
        for r in live:
            k = self.sched.spec_budget(r)
            if self._adepth is not None:
                k = min(k, self._adepth.depth_for(r.rid))
            self.spec_depth_hist[max(k, 0)] = \
                self.spec_depth_hist.get(max(k, 0), 0) + 1
            pr = [int(t) for t in self.draft.propose(r, k)][:max(k, 0)]
            props[r.rid] = pr
            toks[r.slot, 0] = r.pending
            toks[r.slot, 1:1 + len(pr)] = pr
            pos[r.slot] = r.cached
            n_write[r.slot] = 1 + len(pr)
            temps[r.slot] = r.params.temperature
            bkeys[r.slot] = self._base_keys[r.rid]
            tbl[r.slot] = self.cache.table[r.slot]
        tgt, ok, pools = self._verify_jit(
            self.params, self.cache.pools, jnp.asarray(tbl),
            jnp.asarray(pos), jnp.asarray(toks), jnp.asarray(n_write),
            jnp.asarray(temps), jnp.stack(bkeys), jnp.asarray(poison))
        self.cache.pools = pools
        tgt, ok = np.asarray(tgt), np.asarray(ok)
        self._consec_drops = 0
        n_tokens = 0
        for r in live:
            pr = props[r.rid]
            if not ok[r.slot, :1 + len(pr)].all():
                # NaN/Inf anywhere in the rows this walk could consume:
                # quarantine the whole row set, as vanilla decode would
                self._quarantine(r, "nan_logits")
                self._release_draft(r.rid)
                continue
            r.retries = 0
            n_acc = 0
            for i in range(len(pr) + 1):
                # the target's own sample for position cached + 1 + i —
                # identical to what i sequential decode steps would emit
                t_i = int(tgt[r.slot, i])
                r.cached += 1
                self._emit(r, t_i, events)
                n_tokens += 1
                acc = i < len(pr) and pr[i] == t_i
                if acc:
                    n_acc += 1
                if r.state != DECODE or not acc:
                    break
            # rejected rows need no undo: cached simply didn't advance
            # over them, their KV sits masked above the valid length in
            # blocks this request exclusively owns
            self.counters["spec_proposed"] += len(pr)
            self.counters["spec_accepted"] += n_acc
            self.counters["spec_rejected"] += len(pr) - n_acc
            if len(pr) > n_acc:
                self.counters["spec_rollbacks"] += 1
            if self._adepth is not None:
                self._adepth.observe(r.rid, n_acc, len(pr))
            if r.done:
                self._release_draft(r.rid)
            else:
                self.draft.observe(r, n_acc, len(pr))
        return n_tokens

    def run(self, max_steps: int = 100_000) -> Dict[int, np.ndarray]:
        """Drive ``step`` until every submitted request reaches a terminal
        state; returns {rid: emitted token array} (partial streams for
        expired/failed requests, empty for rejected)."""
        for _ in range(max_steps):
            if self.sched.idle:
                break
            self.step()
        else:
            raise RuntimeError("engine did not drain (scheduling bug?)")
        self.release_faults()
        return {rid: np.asarray(r.emitted, np.int32)
                for rid, r in self.requests.items()}

    def stream(self, rid: int):
        """Yield ``rid``'s tokens as they are produced (drives step())."""
        req = self.requests[rid]
        emitted = 0
        while True:
            while emitted < len(req.emitted):
                yield req.emitted[emitted]
                emitted += 1
            if req.done:
                break
            self.step()

    # ------------------------------------------------------ legacy facade
    def generate(self, batch, n_tokens: int, rng=None, temperature=0.0):
        """Fixed-slot-compatible convenience: submit every row of
        ``batch["tokens"]``, drain, return (B, n_tokens) tokens."""
        toks = np.asarray(batch["tokens"])
        seeds = []
        for b in range(toks.shape[0]):
            if rng is None:
                seeds.append(b)
            else:
                seeds.append(int(np.asarray(
                    jax.random.fold_in(rng, b))[-1]) & 0x7FFFFFFF)
        rids = [self.submit(toks[b], max_new_tokens=n_tokens,
                            temperature=float(temperature), seed=seeds[b])
                for b in range(toks.shape[0])]
        out = self.run()
        return jnp.asarray(np.stack([out[r][:n_tokens] for r in rids]))

    # ---------------------------------------------------------- telemetry
    def stats(self) -> dict:
        """One flat counter dict: scheduler occupancy, pool/cache
        counters, and the robustness counters (shed, retried, quarantined,
        expired, watchdog trips, audit passes, per-kind injected
        faults)."""
        sc = self.sched.counters
        out = {
            "n_preemptions": self.sched.n_preemptions,
            "steps": self.sched.step_count,
            "running": len(self.sched.running),
            "waiting": len(self.sched.waiting),
            "free_blocks": self.cache.allocator.n_free,
            "usable_blocks": self.cache.allocator.n_usable,
            "cache_blocks": self.cache.n_cache_blocks,
            **self.cache.counters,
            "shed": sc["shed"],
            "expired": sc["expired"],
            "failed": sc["failed"],
            "storm_preempts": sc["storm_preempts"],
            "watchdog_trips": sc["watchdog_trips"],
            "serial_admission": self.sched.serial_admission,
            **self.counters,
            "spec_acceptance": (self.counters["spec_accepted"]
                                / max(self.counters["spec_proposed"], 1)),
            "spec_depth_hist": dict(sorted(self.spec_depth_hist.items())),
        }
        if self.injector is not None:
            out["faults"] = dict(self.injector.counts)
        if self.cache.prefix is not None:
            out["prefix_cache"] = dict(self.cache.prefix.stats)
        return out


# ==========================================================================
# Legacy fixed-slot engine (dense contiguous cache) — the paged engine's
# differential oracle
# ==========================================================================

@dataclasses.dataclass
class FixedSlotEngine:
    """Batched fixed-slot serving: one prefill + a dense contiguous KV
    cache, stepped one token at a time.  The cache is padded with
    ``n_tokens`` of headroom and decode gets per-request ``(B,)``
    positions, fixing the seed behavior (ring-buffer wrap silently
    overwrote the oldest prompt tokens, and the shared scalar position
    mis-masked mixed-length batches)."""
    model: object
    params: dict

    def __post_init__(self):
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode)

    def generate(self, batch, n_tokens: int, rng=None, temperature=0.0):
        """batch: prefill inputs. Returns (tokens (B, n_tokens), last
        logits)."""
        logits, cache = self._prefill(self.params, batch)
        if not cache or "state" in cache:
            raise ValueError("FixedSlotEngine serves attention-cache "
                             "decoders only")
        S0 = next(cache[k].shape[2] for k in _PAD_KEYS if k in cache)
        # headroom so the ring buffer never wraps, rounded up so the padded
        # seq length stays divisible by the sequence shards
        n_sh = 1
        for ax in self.model.rt.par.seq_axes:
            n_sh *= dict(zip(self.model.rt.mesh.axis_names,
                             self.model.rt.mesh.devices.shape))[ax]
        pad = -(-(S0 + n_tokens) // n_sh) * n_sh - S0
        cache = {k: (jnp.pad(v, [(0, 0), (0, 0), (0, pad)] +
                             [(0, 0)] * (v.ndim - 3))
                     if k in _PAD_KEYS else v)
                 for k, v in cache.items()}
        B = batch["tokens"].shape[0]
        outs = []
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        for i in range(n_tokens):
            outs.append(tok)
            pos = jnp.full((B,), S0 + i, jnp.int32)
            logits, cache = self._decode(
                self.params, cache, {"token": tok, "pos": pos})
            lf = logits[:, -1].astype(jnp.float32)
            if temperature > 0 and rng is not None:
                rng, k = jax.random.split(rng)
                tok = jax.random.categorical(k, lf / temperature)[:, None]
                tok = tok.astype(jnp.int32)
            else:
                tok = jnp.argmax(lf, axis=-1)[:, None].astype(jnp.int32)
        return jnp.concatenate(outs, axis=1), logits
