"""Serving engine: continuous batching over a paged quantized KV-cache.

One :class:`ServeEngine` owns the arena, the scheduler, and the jitted
model entry points:

* **prefill** — per-request, one jitted full-sequence forward per padded
  prompt length (:func:`repro.models.transformer.prefill_paged`): the
  whole prompt's K/V lands in the arena in one pass, and the last
  position's logits yield the first generated token.  Running prefill at
  B=1 is also what makes a request's stochastic-rounding draws
  independent of what else is packed alongside it.
* **decode** — ONE jitted step over the packed slot batch
  (:func:`repro.models.transformer.decode_step_paged`), per-slot
  positions and page tables, greedy argmax.  Empty slots are inert:
  page-table rows of -1 drop their cache writes and the current-token
  key slot keeps their softmax finite; their outputs are ignored.

Quantizer-noise keying: slot ``s`` decoding position ``p`` uses
``fold_in(fold_in(PRNGKey(seed), rid), p)`` (then per-layer and k/v-tag
folds inside the model) — a function of the REQUEST, never of the slot
index or batch occupancy, so greedy tokens are bit-identical whether the
request runs alone or packed (tested).

Multi-device mode (``mesh=`` + ``exchange=``): the arena gains a leading
device axis sharded over ``data``; each device folds its axis index into
the write keys, so K devices hold K independently-quantized caches of
the same sequences — an ensemble over quantization noise.  Each decode
step aggregates per-device logits through the SAME Exchange seam
training uses (``ex.pmean_tree``), which is what puts serving traffic
under ``wire_bytes``/``coded_bits_est`` accounting: the engine's
analytic per-step bytes are asserted equal to the trace-time recorder on
8 forced host devices in CI.

Hardened runtime (``guard=True``; DESIGN §11) — the PR 6 train-step
fault-tolerance discipline applied to decode:

* **Decode guard.**  Each wave computes a per-slot finiteness flag over
  the logits the argmax consumes; in multi-device mode the flag is
  psum'd across the quantization ensemble, so ONE device's non-finite
  row vetoes the slot fleet-wide (the PR 6 rule).  Rejected slots carry
  their token/pos/cache through unchanged — in-graph via
  ``jnp.where(ok, argmax, token_in)``, and structurally because a
  decode wave only writes the slot's current (page, offset), which the
  retry overwrites.  Healthy slots in the same packed batch commit from
  attempt 0 (the exact clean-run invocation), so their streams stay
  bit-identical under faults — asserted on 8 devices in CI.
* **Bounded re-keyed retry.**  A rejected slot retries up to
  ``guard_retries`` times with a re-salted request key
  (``fold_in(req_key, RETRY_SALT + attempt)``): the stochastic-rounding
  draw is re-sampled, not replayed — a draw-dependent blowup gets a
  fresh draw, a persistent fault keeps failing.  Healthy slots ride
  along inert (-1 page rows: writes dropped, outputs ignored), and the
  exchange state advances only on attempt 0, so retries cannot desync
  the ensemble's adaptive state from a clean run.  After the budget:
  **quarantine** — typed ``quarantined`` eviction, pages freed.
* **Fault injection.**  The same parse-once :class:`FaultSpec` machinery
  train uses: ``nan_logits`` is traced into the decode step (per-slot
  NaN rows at the guard's consumption point), ``slot_drop`` /
  ``page_corrupt`` / ``request_stall`` / ``crash`` are host events
  applied between waves, and ``ckpt_*`` kinds corrupt the engine's own
  snapshots.  Wall-clock for events is the decode-wave index; guard
  retries re-run the same wave, so a persistent event drives quarantine.
* **Crash-safe snapshots.**  Every ``snapshot_every`` waves the engine
  writes (page tables, arena occupancy, scheduler queues, per-request
  committed tokens) through the PR 6 tmp+fsync+rename checkpoint path;
  :meth:`restore_serve` walks back to the newest intact snapshot,
  refuses config-fingerprint mismatches, and resubmits every in-flight
  request from its last committed token (prompt + committed re-prefilled
  into a fresh arena — device state died with the process).
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpointing
from repro.configs.base import ModelConfig
from repro.core import faults as faults_mod
from repro.core.exchange import Exchange, ExchangeConfig, make_exchange
from repro.core.retry import BackoffPolicy
from repro.models import transformer as T
from repro.serve import kv_cache as KVC
from repro.serve.scheduler import Request, RequestResult, Scheduler

Array = jax.Array

#: fold_in salt for re-keyed guard retries (attempt a > 0 uses
#: ``fold_in(req_key, RETRY_SALT + a)``; attempt 0 is the plain request
#: key, so a clean run's draws are untouched by the guard)
RETRY_SALT = 0x9e77
#: fold_in salt de-syncing the exchange key on retry invocations
_RETRY_EX_SALT = 0x0a11
#: snapshot schema version (bumped on layout changes; restore refuses
#: versions it does not understand)
SNAPSHOT_VERSION = 1


def _tree_stack_lead(tree, k: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (k, *a.shape)), tree
    )


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        policy: str = "int8",
        page_size: int = 8,
        n_slots: int = 4,
        max_len: int = 64,
        num_pages: int = 0,  # 0 = fully provision every slot
        seed: int = 0,
        exchange=None,  # ExchangeConfig | Exchange | None
        mesh=None,
        guard: bool = False,
        guard_retries: int = 2,
        fault_spec=None,  # faults.FaultSpec | None
        snapshot_dir: str = "",
        snapshot_every: int = 0,
        stall_patience: int = 8,
        max_queue: int = 0,
        low_watermark: float = 0.0,
        backoff: BackoffPolicy | None = None,
        deadline_default: float | None = None,
        clock=None,
    ):
        if not T.paged_eligible(cfg):
            raise ValueError(
                f"arch {cfg.name!r} ({cfg.arch_type}) has no paged cache; "
                "use the dense decode_step fallback in launch/serve.py"
            )
        blocks_per_seq = -(-max_len // page_size)
        if not num_pages:
            num_pages = n_slots * blocks_per_seq
        self.cfg = cfg
        self.params = params
        self.seed = seed
        self.pc = KVC.make_paged_cache_config(
            cfg, policy, page_size, num_pages, blocks_per_seq
        )
        self.guard = guard
        if guard_retries < 0:
            raise ValueError(f"guard_retries must be >= 0, got {guard_retries}")
        self.guard_retries = guard_retries
        if fault_spec is not None and not fault_spec.events:
            fault_spec = None
        if fault_spec is not None:
            for e in fault_spec.events:
                if e.kind not in faults_mod.SERVE_SCOPE:
                    raise ValueError(
                        f"fault kind {e.kind!r} is not a serve fault; "
                        f"serve accepts: {faults_mod.SERVE_SCOPE}"
                    )
        self.fault_spec = fault_spec
        self._inject_logits = (
            fault_spec is not None and fault_spec.has_serve_device_events
        )
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.stall_patience = stall_patience
        self._sched_opts = dict(
            max_queue=max_queue, low_watermark=low_watermark,
            backoff=backoff, deadline_default=deadline_default, clock=clock,
        )
        self.allocator = KVC.PageAllocator(num_pages)
        self.sched = Scheduler(n_slots, page_size, blocks_per_seq,
                               self.allocator, **self._sched_opts)
        self.n_slots = n_slots
        self.mesh = mesh
        self.ex: Exchange | None = (
            make_exchange(exchange) if isinstance(exchange, ExchangeConfig)
            else exchange
        )
        if (self.ex is None) != (mesh is None):
            raise ValueError("multi-device serving needs BOTH exchange and mesh")
        self._root_key = jax.random.PRNGKey(seed)
        self._zero_key = np.zeros_like(np.asarray(self._root_key))
        self.wire_bytes = 0.0
        self.coded_bits = 0.0
        self._prefill_jits: dict = {}
        self._stalled_rids: set = set()
        self._committed: dict[int, list] = {}  # rid -> pre-restart tokens
        if self.ex is None:
            self.cache = KVC.init_paged_cache(self.pc)
            self._decode = jax.jit(self._decode_local, donate_argnums=(0,))
        else:
            self.axis = self.ex.cfg.axis_name
            self.K = mesh.shape[self.axis]
            self.ex_state = self._fresh_ex_state()
            self.cache = _tree_stack_lead(KVC.init_paged_cache(self.pc), self.K)
            self._decode = jax.jit(self._make_dist_decode(), donate_argnums=(0,))
            # analytic operand bytes of the per-step logit exchange — the
            # serving counterpart of the train step's wire_bytes metric
            logits_like = {
                "logits": jnp.zeros((n_slots, cfg.vocab_size), jnp.float32)
            }
            self.wire_per_step = float(
                self.ex.wire_bytes_tree(logits_like, self.K)
            )

    def _fresh_ex_state(self):
        # placed as the decode step returns it (replicated on the mesh): a
        # host-built state has another type, and the first wave after it
        # would trace and compile the decode step a second time
        return jax.device_put(self.ex.init_state(),
                              NamedSharding(self.mesh, P()))

    # -- jitted entry points -----------------------------------------------

    def _decode_local(self, cache, params, token, pos, page_table, slot_keys,
                      fault_step=None):
        wkeys = jax.vmap(jax.random.fold_in)(slot_keys, pos)
        logits, cache = T.decode_step_paged(
            params, self.cfg, self.pc, cache, token, pos, page_table, wkeys
        )
        if self._inject_logits:
            logits = self.fault_spec.poison_logits(logits, fault_step)
        if self.guard:
            ok = jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = jnp.where(ok, jnp.argmax(logits, axis=-1), token)
            return nxt.astype(jnp.int32), logits, cache, ok
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    def _make_dist_decode(self):
        ex, cfg, pc, axis = self.ex, self.cfg, self.pc, self.axis
        mesh = self.mesh
        guard, inject = self.guard, self._inject_logits
        spec = self.fault_spec

        def core(caches, params, token, pos, page_table, slot_keys,
                 ex_state, key, axis_ix, fault_step=None):
            cache = jax.tree_util.tree_map(lambda a: a[0], caches)
            ix = axis_ix[0]
            wkeys = jax.vmap(jax.random.fold_in)(slot_keys, pos)
            # per-device noise stream -> K independently-quantized caches
            wkeys = jax.vmap(jax.random.fold_in, (0, None))(wkeys, ix)
            logits, cache = T.decode_step_paged(
                params, cfg, pc, cache, token, pos, page_table, wkeys
            )
            out, ex_state = ex.pmean_tree(
                {"logits": logits}, ex_state, key, ix
            )
            agg = out["logits"]
            if inject:
                # injected at the guard's consumption point (post-
                # aggregation): the poison stays exactly per-slot, so
                # healthy rows are mathematically untouched
                agg = spec.poison_logits(agg, fault_step)
            coded = (
                ex.coded_bits_tree({"logits": logits}, ex_state)
                if ex.cfg.compressor == "qgenx" else jnp.float32(0.0)
            )
            caches = jax.tree_util.tree_map(lambda a: a[None], cache)
            if guard:
                # one non-finite row on ONE ensemble member vetoes the
                # slot everywhere — the psum'd PR 6 finiteness flag
                ok_local = (jnp.all(jnp.isfinite(logits), axis=-1)
                            & jnp.all(jnp.isfinite(agg), axis=-1))
                bad = jax.lax.psum((~ok_local).astype(jnp.float32), axis)
                ok = bad == 0
                nxt = jnp.where(ok, jnp.argmax(agg, axis=-1), token)
                return (nxt.astype(jnp.int32), agg, caches, ex_state, coded,
                        ok)
            nxt = jnp.argmax(agg, axis=-1).astype(jnp.int32)
            return nxt, agg, caches, ex_state, coded

        n_out = 6 if guard else 5
        out_specs = (P(), P(), P(axis), P(), P()) + ((P(),) if guard else ())
        assert len(out_specs) == n_out

        def step(caches, params, token, pos, page_table, slot_keys,
                 ex_state, key, fault_step=None):
            axis_ix = jnp.arange(mesh.shape[axis], dtype=jnp.int32)
            in_specs = (P(axis), P(), P(), P(), P(), P(), P(), P(), P(axis))
            args = (caches, params, token, pos, page_table, slot_keys,
                    ex_state, key, axis_ix)
            if fault_step is not None:
                in_specs = in_specs + (P(),)
                args = args + (fault_step,)
            fn = shard_map(
                core,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
            return fn(*args)

        return step

    def _prefill_for(self, s_pad: int, nblk: int):
        """Jitted prefill, cached per padded prompt length."""
        if (s_pad, nblk) not in self._prefill_jits:
            cfg, pc = self.cfg, self.pc
            if self.ex is None:
                def fn(cache, params, tokens, pages, keys):
                    return T.prefill_paged(params, cfg, pc, cache, tokens,
                                           pages, keys)
                self._prefill_jits[(s_pad, nblk)] = jax.jit(
                    fn, donate_argnums=(0,)
                )
            else:
                mesh, axis = self.mesh, self.axis

                def core(caches, params, tokens, pages, keys, axis_ix):
                    cache = jax.tree_util.tree_map(lambda a: a[0], caches)
                    dkeys = jax.vmap(jax.random.fold_in, (0, None))(
                        keys, axis_ix[0]
                    )
                    # prefill logits never read the quantized cache, so
                    # they are identical across devices — no collective
                    logits, cache = T.prefill_paged(
                        params, cfg, pc, cache, tokens, pages, dkeys
                    )
                    return logits, jax.tree_util.tree_map(
                        lambda a: a[None], cache
                    )

                def fn(caches, params, tokens, pages, keys):
                    axis_ix = jnp.arange(mesh.shape[axis], dtype=jnp.int32)
                    sm = shard_map(
                        core, mesh=mesh,
                        in_specs=(P(axis), P(), P(), P(), P(), P(axis)),
                        out_specs=(P(), P(axis)),
                        check_vma=False,
                    )
                    return sm(caches, params, tokens, pages, keys, axis_ix)

                self._prefill_jits[(s_pad, nblk)] = jax.jit(
                    fn, donate_argnums=(0,)
                )
        return self._prefill_jits[(s_pad, nblk)]

    # -- host-side orchestration -------------------------------------------

    def _req_key(self, rid: int) -> np.ndarray:
        return np.asarray(jax.random.fold_in(self._root_key, rid))

    def _retry_key(self, rid: int, attempt: int) -> np.ndarray:
        """Re-salted request key for guard retry ``attempt`` (>= 1): the
        per-position fold inside the model then yields a FRESH
        stochastic-rounding draw instead of replaying the failed one."""
        return np.asarray(jax.random.fold_in(
            jax.random.fold_in(self._root_key, rid), RETRY_SALT + attempt
        ))

    def _prefill_slot(self, slot) -> None:
        plen = len(slot.req.prompt)
        ps = self.pc.page_size
        nblk = -(-plen // ps)
        s_pad = nblk * ps
        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, :plen] = slot.req.prompt
        pages = np.asarray(slot.pages[:nblk], np.int32)[None]
        keys = self._req_key(slot.req.rid)[None]
        fn = self._prefill_for(s_pad, nblk)
        logits, self.cache = fn(
            self.cache, self.params, jnp.asarray(tokens), jnp.asarray(pages),
            jnp.asarray(keys),
        )
        first = int(np.argmax(np.asarray(logits[0, plen - 1])))
        slot.pos = plen
        slot.last_token = first
        slot.out.append(first)

    def _admit_and_prefill(self, events=None) -> None:
        # retire/admit until fixed point: a prefilled request whose budget
        # is a single token retires immediately, freeing pages mid-wave
        while True:
            for i, slot in self.sched.admit():
                self._prefill_slot(slot)
                if events is not None:
                    events.append(("admit", slot.req.rid, i,
                                   self.sched.decode_steps))
            done = self.sched.retire_finished()
            if events is not None:
                for slot in done:
                    events.append(("retire", slot.req.rid, -1,
                                   self.sched.decode_steps))
            if not done:
                return

    def _pack(self, active, attempt: int = 0):
        B = self.n_slots
        token = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        pt = np.full((B, self.pc.blocks_per_seq), -1, np.int32)
        keys = np.broadcast_to(self._zero_key, (B, *self._zero_key.shape)).copy()
        for i, slot in active:
            token[i] = slot.last_token
            pos[i] = slot.pos
            pt[i, : len(slot.pages)] = slot.pages
            keys[i] = (self._req_key(slot.req.rid) if attempt == 0
                       else self._retry_key(slot.req.rid, attempt))
        return (jnp.asarray(token), jnp.asarray(pos), jnp.asarray(pt),
                jnp.asarray(keys))

    def _invoke_decode(self, token, pos, pt, keys, attempt: int = 0):
        """One jitted decode invocation; returns host (next_tokens, ok)
        with ok=None when the guard is off.  Exchange state advances only
        on attempt 0 — retries see the same ensemble state a clean run
        would, so a recovered slot cannot desync later waves."""
        if self.ex is None:
            args = [self.cache, self.params, token, pos, pt, keys]
            if self._inject_logits:
                args.append(jnp.int32(self.sched.decode_steps))
            outs = self._decode(*args)
            if self.guard:
                nxt, _, self.cache, ok = outs
            else:
                nxt, _, self.cache = outs
                ok = None
        else:
            step_key = jax.random.fold_in(
                self._root_key, 0x5e4e + self.sched.decode_steps
            )
            if attempt:
                step_key = jax.random.fold_in(
                    step_key, _RETRY_EX_SALT + attempt
                )
            args = [self.cache, self.params, token, pos, pt, keys,
                    self.ex_state, step_key]
            if self._inject_logits:
                args.append(jnp.int32(self.sched.decode_steps))
            outs = self._decode(*args)
            if self.guard:
                nxt, _, self.cache, new_ex_state, coded, ok = outs
            else:
                nxt, _, self.cache, new_ex_state, coded = outs
                ok = None
            if attempt == 0:
                self.ex_state = new_ex_state
            self.wire_bytes += self.wire_per_step
            self.coded_bits += float(coded)
        return np.asarray(nxt), (None if ok is None else np.asarray(ok))

    def _decode_wave(self, packable, events=None) -> dict:
        """One decode wave over the packed batch with the guard's bounded
        re-keyed retry; returns {slot_index: committed token}.  Slots
        still failing after ``guard_retries`` retries are quarantined
        (typed eviction, pages freed)."""
        committed: dict = {}
        pending = list(packable)
        attempt = 0
        while pending:
            token, pos, pt, keys = self._pack(pending, attempt=attempt)
            nxt, ok = self._invoke_decode(token, pos, pt, keys, attempt)
            if ok is None:  # guard off: every packed slot commits
                for i, _slot in pending:
                    committed[i] = int(nxt[i])
                return committed
            still = []
            for i, slot in pending:
                if ok[i]:
                    committed[i] = int(nxt[i])
                else:
                    still.append((i, slot))
            if not still:
                return committed
            if attempt >= self.guard_retries:
                for i, slot in still:
                    self.sched.evict(i, "quarantined")
                    self._stalled_rids.discard(slot.req.rid)
                    if events is not None:
                        events.append(("evict:quarantined", slot.req.rid, i,
                                       self.sched.decode_steps))
                return committed
            attempt += 1
            self.sched.stats["guard_retries"] = (
                self.sched.stats.get("guard_retries", 0) + len(still)
            )
            pending = still
        return committed

    # -- host fault application (between decode waves) ---------------------

    def _apply_host_faults(self, events=None) -> None:
        spec, step = self.fault_spec, self.sched.decode_steps
        if spec is None:
            return
        if spec.crash_at(step):
            # die the way a real kill does: no cleanup, no final snapshot
            print(f"[serve] fault: crash before decode wave {step}",
                  flush=True)
            os._exit(faults_mod.CRASH_EXIT_CODE)
        hits = spec.slots_hit("slot_drop", step)
        if hits:
            targets = (
                [i for i, _ in self.sched.active()] if None in hits
                else [i for i in hits if self.sched.slots[i] is not None]
            )
            for i in sorted(set(targets)):
                slot = self.sched.evict(i, "dropped")
                self._stalled_rids.discard(slot.req.rid)
                if events is not None:
                    events.append(("evict:dropped", slot.req.rid, i, step))
        hits = spec.slots_hit("page_corrupt", step)
        if hits:
            targets = (
                [i for i, _ in self.sched.active()] if None in hits
                else [i for i in hits if self.sched.slots[i] is not None]
            )
            for i in sorted(set(targets)):
                slot = self.sched.slots[i]
                # corrupt one replica in ensemble mode: the psum'd flag
                # must veto the slot even though K-1 devices are clean
                self.cache = KVC.corrupt_page(
                    self.cache, self.pc, slot.pages[0],
                    lead=self.ex is not None,
                    device=0 if self.ex is not None else None,
                )
                if events is not None:
                    events.append(("fault:page_corrupt", slot.req.rid, i,
                                   step))
        hits = spec.slots_hit("request_stall", step)
        if hits:
            targets = (
                [i for i, _ in self.sched.active()] if None in hits
                else [i for i in hits if self.sched.slots[i] is not None]
            )
            for i in sorted(set(targets)):
                slot = self.sched.slots[i]
                if slot.req.rid not in self._stalled_rids:
                    self._stalled_rids.add(slot.req.rid)
                    if events is not None:
                        events.append(("fault:stall", slot.req.rid, i, step))

    # -- crash-safe snapshots ----------------------------------------------

    def _fingerprint(self) -> dict:
        return {
            "arch": self.cfg.name,
            "cache": self.pc.describe(),
            "page_size": self.pc.page_size,
            "num_pages": self.pc.num_pages,
            "blocks_per_seq": self.pc.blocks_per_seq,
            "n_slots": self.n_slots,
            "seed": self.seed,
            "devices": 1 if self.ex is None else int(self.K),
        }

    def _snapshot_trees(self) -> dict:
        bps = self.pc.blocks_per_seq
        pt = np.full((self.n_slots, bps), -1, np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        for i, slot in self.sched.active():
            pt[i, : len(slot.pages)] = slot.pages
            pos[i] = slot.pos
        occupancy = np.zeros((self.pc.num_pages,), np.int8)
        for _, slot in self.sched.active():
            occupancy[np.asarray(slot.pages, np.int64)] = 1
        return {"serve": {"page_table": pt, "pos": pos,
                          "occupancy": occupancy}}

    def results(self) -> dict:
        """{rid: RequestResult} with pre-restart committed tokens merged
        in front (a resumed request's scheduler-side tokens start at its
        last committed token)."""
        out = {}
        for rid, rr in self.sched.results.items():
            pre = self._committed.get(rid)
            if pre:
                rr = dataclasses.replace(
                    rr, tokens=tuple(pre) + tuple(rr.tokens)
                )
            out[rid] = rr
        return out

    def snapshot(self, path: str) -> int:
        """Write one atomic engine snapshot (npz -> meta -> latest, the
        PR 6 ordering) capturing everything a restart needs: page tables
        + arena occupancy (integrity-checked diagnostics), both scheduler
        queues, terminal results, and per-request committed tokens."""
        sched = self.sched
        now = sched.clock()

        def _ttl_left(deadline, submit_at):
            return None if deadline is None else deadline - (now - submit_at)

        slots_state = []
        for slot in sched.slots:
            if slot is None:
                slots_state.append(None)
                continue
            slots_state.append({
                "rid": slot.req.rid,
                "prompt": [int(t) for t in slot.req.prompt],
                "max_new": int(slot.req.max_new),
                "ttl_left": _ttl_left(slot.req.deadline, slot.submit_at),
                "out": [int(t) for t in slot.out],
                "stalled": slot.req.rid in self._stalled_rids,
            })

        def q_state(q):
            return {
                "rid": q.req.rid,
                "prompt": [int(t) for t in q.req.prompt],
                "max_new": int(q.req.max_new),
                "ttl_left": _ttl_left(q.req.deadline, q.submit_at),
                "attempt": int(q.attempt),
            }

        extra = {
            "serve_snapshot": SNAPSHOT_VERSION,
            "fingerprint": self._fingerprint(),
            "decode_steps": int(sched.decode_steps),
            "slots": slots_state,
            "waiting": [q_state(q) for q in sched.waiting],
            "backoff": [q_state(q) for q in sched.backoff],
            "results": [
                {"rid": int(rr.rid), "kind": rr.kind,
                 "tokens": [int(t) for t in rr.tokens]}
                for rr in self.results().values()
            ],
        }
        step = int(sched.decode_steps)
        checkpointing.save(path, step, self._snapshot_trees(), extra=extra)
        if self.fault_spec is not None:
            for kind in self.fault_spec.ckpt_faults_at(step):
                faults_mod.inject_ckpt_fault(path, step, kind)
        return step

    def restore_serve(self, path: str) -> dict:
        """Resume from the newest intact snapshot at ``path``.

        The arena is rebuilt from scratch (device state died with the
        process): every non-terminal request is resubmitted with
        ``prompt + committed`` as its prompt and the remaining budget, so
        generation continues from the last committed token — the (rid,
        position) noise keying makes the continuation independent of the
        re-packing.  In-flight requests re-enter the queue ahead of
        previously-waiting ones (they were admitted first; FIFO order
        survives the restart).  Returns a summary dict for the caller to
        print ({"step", "in_flight", "waiting", "done"}).
        """
        bps = self.pc.blocks_per_seq
        template = {"serve": {
            "page_table": jnp.zeros((self.n_slots, bps), jnp.int32),
            "pos": jnp.zeros((self.n_slots,), jnp.int32),
            "occupancy": jnp.zeros((self.pc.num_pages,), jnp.int8),
        }}
        step, _trees, _ = checkpointing.restore_with_fallback(path, template)
        meta = checkpointing.read_meta(path, step)
        extra = meta.get("extra", {})
        if extra.get("serve_snapshot") != SNAPSHOT_VERSION:
            raise checkpointing.CheckpointStructureError(
                "serve", f"not a v{SNAPSHOT_VERSION} serve snapshot "
                         f"(got {extra.get('serve_snapshot')!r})"
            )
        fp = extra["fingerprint"]
        if fp != self._fingerprint():
            diff = {k: (fp.get(k), v) for k, v in self._fingerprint().items()
                    if fp.get(k) != v}
            raise checkpointing.CheckpointStructureError(
                "serve", f"snapshot fingerprint mismatch: {diff}"
            )
        self.reset()
        sched = self.sched
        sched.decode_steps = int(extra["decode_steps"])
        for r in extra["results"]:
            rr = RequestResult(rid=int(r["rid"]), kind=r["kind"],
                               tokens=tuple(int(t) for t in r["tokens"]))
            sched.results[rr.rid] = rr
            sched.stats[rr.kind] = sched.stats.get(rr.kind, 0) + 1
        in_flight = done = 0
        resumed: list[Request] = []

        def _revive(st, was_active: bool):
            nonlocal in_flight, done
            rid = int(st["rid"])
            committed = [int(t) for t in st["out"]] if was_active else []
            remaining = int(st["max_new"]) - len(committed)
            if committed:
                self._committed[rid] = committed
            if was_active and remaining <= 0:
                # budget already spent: terminal, nothing to decode
                sched.results[rid] = RequestResult(
                    rid=rid, kind="ok", tokens=tuple(committed))
                sched.stats["ok"] = sched.stats.get("ok", 0) + 1
                done += 1
                return
            prompt = [int(t) for t in st["prompt"]] + committed
            resumed.append(Request(rid=rid, prompt=prompt, max_new=remaining,
                                   deadline=st["ttl_left"]))
            if was_active:
                in_flight += 1
                if st.get("stalled"):
                    self._stalled_rids.add(rid)

        for st in extra["slots"]:
            if st is not None:
                _revive(st, was_active=True)
        for st in list(extra["waiting"]) + list(extra["backoff"]):
            st = dict(st, out=[])
            _revive(st, was_active=False)
        for req in resumed:
            sched.submit(req)
        return {"step": step, "in_flight": in_flight,
                "waiting": len(extra["waiting"]) + len(extra["backoff"]),
                "done": done,
                "committed": {r: len(t) for r, t in self._committed.items()}}

    # -- the decode loop ---------------------------------------------------

    def run(self, requests, events=None, _stop_after=None) -> dict:
        """Drive every request to a terminal outcome; returns {rid: out
        tokens} for requests that finished ``ok`` (the full typed picture
        — quarantined / dropped / shed / timed-out — is in
        :meth:`results`).

        ``events`` (optional list) collects ("admit"|"retire"|
        "evict:KIND"|"fault:KIND", rid, slot, decode_step) tuples — the
        admission/fault evidence the tests and the serve CLI print.
        ``_stop_after`` (test hook) abandons the loop after that many
        decode waves, simulating an abrupt stop: state past the last
        snapshot is lost, exactly like a kill.
        """
        for r in requests:
            self.sched.submit(r)
        self._admit_and_prefill(events)
        idle_spins = 0
        while self.sched.has_work():
            self._apply_host_faults(events)
            for i, slot, kind in self.sched.expire_active(self.stall_patience):
                self._stalled_rids.discard(slot.req.rid)
                if events is not None:
                    events.append((f"evict:{kind}", slot.req.rid, i,
                                   self.sched.decode_steps))
            self._admit_and_prefill(events)
            if not self.sched.has_work():
                break
            packable = [
                (i, s) for i, s in self.sched.active()
                if s.req.rid not in self._stalled_rids
            ]
            if not packable:
                if self.sched.active():
                    # every active slot is stalled: let the wave clock
                    # tick so stall_patience / deadlines can evict them
                    self.sched.decode_steps += 1
                    continue
                # nothing active at all: only backoff-delayed work is
                # left — waiting out the delay would idle the engine
                if self.sched.force_readmit():
                    idle_spins += 1
                    if idle_spins <= self.n_slots + len(self.sched.backoff) + 1:
                        continue
                raise RuntimeError(
                    "scheduler stalled: queued requests but nothing active "
                    f"(waiting={len(self.sched.waiting)} "
                    f"backoff={len(self.sched.backoff)} "
                    f"free_pages={self.allocator.n_free})"
                )
            idle_spins = 0
            committed = self._decode_wave(packable, events)
            self.sched.decode_steps += 1
            for i, t in committed.items():
                slot = self.sched.slots[i]
                if slot is None:
                    continue  # evicted between commit and here (host fault)
                slot.out.append(t)
                slot.last_token = t
                slot.pos += 1
                slot.last_progress = self.sched.decode_steps
            if (self.snapshot_dir and self.snapshot_every
                    and self.sched.decode_steps % self.snapshot_every == 0):
                self.snapshot(self.snapshot_dir)
            if (_stop_after is not None
                    and self.sched.decode_steps >= _stop_after):
                return {rid: list(rr.tokens)
                        for rid, rr in self.results().items() if rr.ok}
            self._admit_and_prefill(events)
        return {rid: list(rr.tokens)
                for rid, rr in self.results().items() if rr.ok}

    def reset(self) -> None:
        """Empty the engine (fresh scheduler + arena bookkeeping) while
        keeping the compiled decode/prefill entry points.

        The cache arrays themselves are NOT cleared: stale pages are dead
        by construction — a slot only reads positions below its own
        ``pos`` through its own page table, and prefill overwrites every
        page it is granted.  This is what lets the serve benchmark time
        warm steady-state runs with compilation excluded.
        """
        self.allocator = KVC.PageAllocator(self.pc.num_pages)
        self.sched = Scheduler(
            self.n_slots, self.pc.page_size, self.pc.blocks_per_seq,
            self.allocator, **self._sched_opts,
        )
        self.wire_bytes = 0.0
        self.coded_bits = 0.0
        self._stalled_rids = set()
        self._committed = {}
        if self.ex is not None:
            self.ex_state = self._fresh_ex_state()

    @property
    def cache_bytes(self) -> int:
        """Arena bytes per device (the quantization win the bench reports)."""
        return KVC.cache_bytes(self.pc)

    @property
    def fp32_cache_bytes(self) -> int:
        return KVC.fp32_cache_bytes(self.pc)
