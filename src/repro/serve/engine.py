"""Serving engine: prefill/decode steps + continuous-batching scheduler.

The engine runs a fixed number of *slots* (the compiled batch dimension);
requests stream through slots as they finish (continuous batching).  Decode
steps take per-slot positions, so slots never run in lockstep.

Per-family notes: dense/moe/vlm use the KV cache; ssm/hybrid carry O(1)
recurrent state (their ``pos`` only drives RoPE in the hybrid's shared
attention).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.quant.bitnet import TernaryWeight, ternary_weight

# Host spans on the profiler's clock (recorded only while a profiler trace
# is on): one per engine phase, opened by the helper that runs the phase so
# the legacy and in-flight steps share them.
ADMIT_SPAN = "serve.admit"
CHUNKS_SPAN = "serve.chunks"
PREFILL_CHUNK_SPAN = "serve.prefill_chunk"
ENSURE_KV_SPAN = "serve.ensure_kv"
DECODE_SPAN = "serve.decode"
OBSERVERS_SPAN = "serve.observers"
SAMPLE_SPAN = "serve.sample"
SYNC_SPAN = "serve.sync"
FINISH_SPAN = "serve.finish"


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # [len] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Ended by the cache window (slot.pos hit max_seq), not by EOS or the
    # token budget — a cut-off output, not a natural completion.
    truncated: bool = False
    # Refused by the admission policy (never prefilled; no output).
    refused: bool = False
    # Times this request was evicted under KV-page pressure and re-queued
    # for re-prefill (paged engines only; see repro.serve.paged_kv).
    preempted: int = 0


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    pos: int = 0                       # next write position in the cache
    # In-flight (chunked) prefill state: prompt tokens already written into
    # the staging cache, and the single-lane staging cache itself (None
    # once the slot is decode-ready or free).
    filled: int = 0
    staging: object = None
    # The token array being prefilled: the request's prompt, or — after a
    # preemption — prompt + output[:-1] (the resume re-prefill; the last
    # sampled token stays the decode feed).  None once decode-ready.
    tokens: Optional[np.ndarray] = None


def _is_ternary(x) -> bool:
    return isinstance(x, TernaryWeight)


def _is_projection(name: str) -> bool:
    return name.startswith("in_proj") or name in (
        "wq", "wk", "wv", "wo", "w1", "w2", "w3", "out_proj", "wkv_a",
        "wkv_b")


def prepare_params(params):
    """Offline weight transform for serving: each BitNet projection becomes
    a :class:`~repro.quant.bitnet.TernaryWeight` (int8 codes, one absmean
    gamma per stacked layer, and per expert of a held-expert layer),
    quantized ONCE here so the serve step runs the integer BitLinear and
    never re-quantizes a weight.  Routers, norms, the embedding and an
    untied head stay as they are.  Leaves already in that form pass
    through, so the transform is idempotent (the packed 2-bit variant goes
    further via kernels/bitlinear)."""
    def q(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        if (not _is_ternary(leaf) and leaf.ndim >= 2
                and _is_projection(keys[-1])):
            # layer params are stacked [L, ...] under "blocks" (and
            # "dense_blocks"); a shared block (the hybrid's) is one weight
            # applied whole
            return ternary_weight(
                leaf, stacked=any(k.endswith("blocks") for k in keys),
                experts="experts" in keys)
        return leaf

    return jax.tree_util.tree_map_with_path(q, params, is_leaf=_is_ternary)


class ServeEngine:
    """Continuous-batching engine over a registry ModelAPI."""

    def __init__(self, api, params, *, max_slots: int = 4,
                 max_seq: int = 512, greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0,
                 metrics=None, prefill_chunk_tokens: Optional[int] = None,
                 admission=None, paged_kv=None):
        if api.decode is None:
            raise ValueError(f"{api.cfg.name} is encoder-only; no decode")
        if prefill_chunk_tokens is not None:
            if prefill_chunk_tokens < 1:
                raise ValueError(
                    f"prefill_chunk_tokens must be >= 1, got "
                    f"{prefill_chunk_tokens}"
                )
            if getattr(api, "prefill_chunk", None) is None:
                raise ValueError(
                    f"{api.cfg.name} has no chunked prefill "
                    f"(api.prefill_chunk is None); only decoder "
                    f"transformers support in-flight batching"
                )
        self.api = api
        self.cfg = api.cfg
        if self.cfg.quantization == "bitnet":
            # quantize the projections once, here; the engine keeps only
            # the prepared tree, so the latent weights are not held
            params = prepare_params(params)
        self.params = params
        # projection leaves served as int8 ternary codes (7 for a BitNet
        # transformer, 0 for a model that is not quantized)
        self.ternary_projection_leaves = sum(
            map(_is_ternary, jax.tree.leaves(params, is_leaf=_is_ternary)))
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.slots = [_Slot() for _ in range(max_slots)]
        self.cache = api.init_cache(max_slots, max_seq)
        self._next_uid = 0
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        # Requests the admission policy refused outright (never prefilled;
        # not in ``finished`` — refusal is not a completion).
        self.refused: List[Request] = []
        # In-flight batching: chunk prefill into fixed token-budget slices
        # and merge them with the batched decode slots into ONE engine step
        # (TensorRT-LLM's in-flight batching).  None = legacy mode: whole
        # prompts prefill alone at admission.
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # Live admission policy (duck-typed: ``decide(engine, request) ->
        # "admit" | "defer" | "refuse"``, e.g. repro.serve.admission
        # .LiveAdmission).  None admits whenever a slot is free.
        self.admission = admission
        # Paged KV mode (repro.serve.paged_kv.PagedKVCache): requests pin
        # whole pages at admission, decode steps extend page-by-page, and
        # page-pool exhaustion preempts the lowest-priority running slot
        # (pages freed, request re-queued at the head for re-prefill).
        # None = the idealized contiguous max_slots x max_seq layout.
        self.paged_kv = paged_kv
        self.preemptions = 0
        if paged_kv is not None:
            alloc = paged_kv.allocator
            if alloc.pages_needed(max_seq) > alloc.total_pages:
                # a lone request could then never grow to max_seq — the
                # preemption loop would starve with nothing left to evict
                raise ValueError(
                    f"page pool of {alloc.total_pages} x "
                    f"{alloc.page_tokens}-token pages cannot back one "
                    f"max_seq={max_seq} request; need >= "
                    f"{alloc.pages_needed(max_seq)} pages"
                )
        # Step observers: called after every prefill / batched decode with a
        # small event dict — the hook accelerator backends attach to (e.g.
        # repro.serve.legion_backend drives the projection GEMMs of each
        # step through the Legion runtime for traffic/cycle tallies).
        #   {"kind": "prefill", "uid": int, "tokens": prompt_len,
        #    "done": bool}              # completed at its prompt boundary
        #   {"kind": "decode",  "uids": [int, ...], "tokens": 1,
        #    "positions": [int, ...]}   # per-slot cache write position —
        #                               # the step attended pos+1 entries
        #                               # (context length for act-to-act
        #                               # attention lowering)
        # In-flight mode emits ONE merged event per engine step instead:
        #   {"kind": "step",
        #    "chunks": [{"uid", "tokens", "pos0", "last", "done"}, ...],
        #    "uids": [...], "tokens": 1, "positions": [...]}
        # where each chunk wrote ``tokens`` prompt tokens at offset
        # ``pos0`` (attending pos0+tokens cache entries), "last" marks a
        # prompt-completing chunk and "done" a request that finished at
        # admission (EOS / budget / window) without taking a decode slot;
        # "uids"/"positions" are the step's batched decode exactly as in
        # the legacy decode event.  A model with held experts adds to the
        # decode (or step) event, once the step's tokens reach the host,
        # "moe_routed" (tokens of the active slots routed to held experts,
        # summed over the expert layers) and "moe_experts_hit" (the
        # (layer, held expert) pairs they reached).
        self.step_observers: List[Callable[[dict], None]] = []
        # Batch occupancy per decode step (len(uids) of each event): how
        # full the continuous batch actually ran — the denominator behind
        # engine-view per-step latencies (serve_pipeline benchmark).
        self.decode_batch_sizes: List[int] = []
        # Per-step phase + active-slot history covering prefill AND decode
        # ({"phase", "slots", "tokens"[, "uid"]}) — the occupancy series
        # the load harness reads; admission bursts show up as runs of
        # prefill entries that decode_batch_sizes alone never records.
        self.step_log: List[Dict[str, int]] = []
        # Duck-typed metrics registry (see repro.obs.metrics
        # .MetricsRegistry); None disables serve_* metric emission.
        self.metrics = metrics
        if metrics is not None:
            metrics.gauge("serve_ternary_projection_leaves").set(
                self.ternary_projection_leaves)
        # named functions, so the compiled modules are jit_serve_decode and
        # jit_serve_prefill_chunk in a profiler trace.  Each donates the
        # cache it is given, which its successor then updates in place:
        # nothing else may hold a cache passed to one of them.
        def serve_decode(params, tok, cache, pos):
            return api.decode(params, tok, cache, pos)

        def serve_prefill_chunk(params, toks, cache, pos0):
            return api.prefill_chunk(params, toks, cache, pos0)

        self._decode = jax.jit(serve_decode, donate_argnums=(2,))
        self._prefill_chunk = None
        if prefill_chunk_tokens is not None:
            # jit caches by chunk shape; the fixed budget bounds the set of
            # chunk lengths (budget + the per-prompt remainders)
            self._prefill_chunk = jax.jit(serve_prefill_chunk,
                                          donate_argnums=(2,))

    # ------------------------------------------------------------------ #
    def submit(self, prompt: np.ndarray, *, max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> Request:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D token array; got shape "
                f"{prompt.shape}"
            )
        if len(prompt) > self.max_seq:
            # dynamic_update_slice would clamp the cache write and the
            # engine would decode over a corrupted lane — reject up front
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds max_seq="
                f"{self.max_seq}; it can never fit a cache lane"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        # monotonic uid: len(queue)+len(finished) collides once requests sit
        # in slots (neither queued nor finished), merging distinct requests
        # wherever uid keys a map (e.g. legion_backend.per_request)
        req = Request(uid=self._next_uid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id)
        self._next_uid += 1
        self.queue.append(req)
        return req

    # ------------------------------------------------------------------ #
    def _next_admittable(self) -> Optional[Request]:
        """Pop the next queue entry past the admission policy.

        Refusals pop, flag, and land in :attr:`refused`; a deferral stops
        admission for this step (the queue head stays put).  Both are
        counted in ``step_log`` and the metrics registry.
        """
        while self.queue:
            req = self.queue[0]
            action = ("admit" if self.admission is None
                      else self.admission.decide(self, req))
            if action == "admit":
                return self.queue.pop(0)
            if action == "refuse":
                self.queue.pop(0)
                req.refused = True
                req.done = True
                self.refused.append(req)
                self.step_log.append({"phase": "refuse", "uid": req.uid,
                                      "tokens": len(req.prompt),
                                      "slots": len(self._active())})
                if self.metrics is not None:
                    self.metrics.counter("serve_admission_refused").inc()
                continue
            if action == "defer":
                self.step_log.append({"phase": "defer", "uid": req.uid,
                                      "tokens": len(req.prompt),
                                      "slots": len(self._active())})
                if self.metrics is not None:
                    self.metrics.counter("serve_admission_deferred").inc()
                return None
            raise ValueError(
                f"admission policy returned {action!r}; expected 'admit', "
                f"'defer' or 'refuse'"
            )
        return None

    def _first_token(self, req: Request, tok: int, plen: int) -> bool:
        """Record the prefill-sampled token and apply the prompt-boundary
        completion rules: EOS sampled at prefill, a 1-token budget, or a
        prompt filling the whole cache window all finish the request here —
        it never occupies a decode slot.  Returns True if finished."""
        req.output.append(tok)
        hit_eos = req.eos_id is not None and tok == req.eos_id
        full = plen >= self.max_seq   # no cache row left for a decode write
        if req.max_new_tokens <= 1 or hit_eos or full:
            req.done = True
            req.truncated = full and not hit_eos and req.max_new_tokens > 1
            self.finished.append(req)
            return True
        return False

    def _log_prefill(self, req: Request, plen: int, *,
                     count_tokens: bool = True) -> None:
        self.step_log.append({"phase": "prefill", "uid": req.uid,
                              "tokens": plen,
                              "slots": len(self._active())})
        if self.metrics is not None:
            self.metrics.counter("serve_prefill_steps").inc()
            if count_tokens:
                self.metrics.counter("serve_prefill_tokens").inc(plen)
            self.metrics.histogram("serve_prompt_tokens").observe(plen)
            self.metrics.gauge("serve_slot_occupancy").set(
                len(self._active()) / self.max_slots)

    # ---- paged-KV plumbing (no-ops when self.paged_kv is None) -------- #
    @staticmethod
    def _resume_tokens(req: Request) -> np.ndarray:
        """The tokens a (re-)prefill writes: the prompt, plus — after a
        preemption — every sampled token but the last (which stays the
        decode feed, exactly as if the eviction never happened)."""
        if not req.output:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.output[:-1], np.int32)])

    def _page_admit(self, req: Request, tokens: int) -> bool:
        """Pin the request's prefill pages; on pool shortfall the request
        returns to the queue head (admission waits for pages, it does not
        preempt — only decode-side growth does)."""
        if self.paged_kv is None:
            return True
        if self.paged_kv.admit(req.uid, tokens):
            return True
        self.queue.insert(0, req)
        self.step_log.append({"phase": "defer_page", "uid": req.uid,
                              "tokens": tokens,
                              "slots": len(self._active())})
        if self.metrics is not None:
            self.metrics.counter("serve_page_deferred").inc()
        return False

    def _page_release(self, req: Request) -> None:
        if self.paged_kv is not None and self.paged_kv.holds(req.uid):
            self.paged_kv.release(req.uid)

    def _preempt(self, i: int) -> None:
        """Evict slot ``i``: free its pages, count the preemption, and
        re-queue the request at the head for chunked re-prefill."""
        slot = self.slots[i]
        req = slot.request
        self.paged_kv.evict(req.uid)
        req.preempted += 1
        self.preemptions += 1
        slot.request = None
        slot.pos = 0
        slot.filled = 0
        slot.staging = None
        slot.tokens = None
        self.queue.insert(0, req)
        self.step_log.append({"phase": "preempt", "uid": req.uid,
                              "tokens": len(req.output),
                              "slots": len(self._active())})
        if self.metrics is not None:
            self.metrics.counter("serve_preempted_total").inc()
        st = getattr(self.admission, "stats", None)
        if st is not None and hasattr(st, "preempted"):
            st.preempted += 1

    def _preempt_victim(self, exclude_uid: int) -> Optional[int]:
        """Slot index to evict: the latest-admitted page holder other
        than ``exclude_uid`` (lowest-priority running request)."""
        by_uid = {s.request.uid: i for i, s in enumerate(self.slots)
                  if s.request is not None}
        for uid in self.paged_kv.eviction_order():
            if uid != exclude_uid and uid in by_uid:
                return by_uid[uid]
        return None

    def _ensure_kv(self, active: List[int]) -> List[int]:
        """Grow every decoding slot's page reservation to cover this
        step's cache write (``pos + 1`` tokens), evicting lower-priority
        slots under pool pressure.  Returns ``active`` minus any slots
        preempted along the way."""
        with TraceAnnotation(ENSURE_KV_SPAN):
            if self.paged_kv is None:
                return active
            for i in active:
                slot = self.slots[i]
                if slot.request is None:     # preempted by an earlier slot
                    continue
                uid = slot.request.uid
                while not self.paged_kv.extend(uid, slot.pos + 1):
                    victim = self._preempt_victim(uid)
                    if victim is None:
                        raise RuntimeError(
                            f"request {uid} cannot grow its KV pages with "
                            f"nothing left to evict (pool too small?)"
                        )
                    self._preempt(victim)
            if self.metrics is not None:
                st = self.paged_kv.stats()
                self.metrics.gauge("serve_page_pinned").set(st.pinned_pages)
                self.metrics.gauge("serve_page_free").set(st.free_pages)
                self.metrics.gauge("serve_page_waste_tokens").set(
                    st.waste_tokens)
            return [i for i in active if self.slots[i].request is not None]

    def _admit(self):
        """Fill free slots from the queue; prefill each admitted request.

        Legacy (whole-prompt) path: each admitted prompt prefills alone.
        Requests finishing at their prompt boundary (see
        :meth:`_first_token`) complete here and leave the slot free for
        the next queue entry.  Paged engines pin the prefill's pages
        first (a shortfall leaves the request queued) and re-prefill
        ``prompt + output[:-1]`` for requests resuming after preemption.
        """
        with TraceAnnotation(ADMIT_SPAN):
            for i, slot in enumerate(self.slots):
                if slot.request is not None:
                    continue
                while True:
                    req = self._next_admittable()
                    if req is None:
                        return
                    tokens = self._resume_tokens(req)
                    plen = len(tokens)
                    if not self._page_admit(req, plen):
                        return
                    resume = bool(req.output)
                    # single-request prefill into this slot's cache lane
                    single_cache = self.api.init_cache(1, self.max_seq)
                    logits, single_cache = self.api.prefill(
                        self.params,
                        {"tokens": jnp.asarray(tokens[None, :])},
                        single_cache,
                    )
                    finished = False
                    if resume:
                        # the re-prefill's sampled token is the one already
                        # at output[-1] (same cache prefix) — drop it, resume
                        # the decode loop where the eviction cut it off
                        pass
                    else:
                        tok = self._sample(logits[:, -1])
                        finished = self._first_token(req, int(tok[0]), plen)
                    if not finished:
                        self._land(i, single_cache, req.uid, plen)
                        slot.request = req
                        slot.pos = plen
                    else:
                        self._page_release(req)
                    self._log_prefill(req, plen)
                    self._notify({"kind": "prefill", "uid": req.uid,
                                  "tokens": plen, "done": finished})
                    if not finished:
                        break          # slot taken; move to the next free one

    def _land(self, i: int, lane, uid: int, tokens: int) -> None:
        """Write a prefilled 1-lane cache into lane ``i`` of the batch
        cache, in place (through the page reservation in paged mode)."""
        cache = self.cache
        if self.paged_kv is not None:
            self.cache = self.paged_kv.write_slot(cache, lane, i, uid=uid,
                                                  tokens=tokens)
        else:
            self.cache = _write_slot(cache, lane, i)
        self._count_in_place(cache, batch=True)

    def _count_in_place(self, donated, *, batch: bool = False) -> None:
        """Count a call that consumed ``donated`` in place: once the call is
        dispatched every leaf is deleted, its buffer now the result's (a
        leaf the program could not alias stays alive).  ``batch`` marks the
        engine's batch cache, whose bytes the gauge holds.  No sync."""
        if self.metrics is None:
            return
        leaves = jax.tree.leaves(donated)
        if not all(leaf.is_deleted() for leaf in leaves):
            return
        self.metrics.counter("serve_cache_inplace_calls").inc()
        if batch:
            self.metrics.gauge("serve_cache_inplace_bytes").set(
                sum(leaf.nbytes for leaf in leaves))

    def _sample(self, logits: jnp.ndarray) -> jnp.ndarray:
        if self.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(
            sub, logits / self.temperature, axis=-1
        ).astype(jnp.int32)

    def _active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.request is not None]

    def _notify(self, event: dict) -> None:
        with TraceAnnotation(OBSERVERS_SPAN):
            for fn in self.step_observers:
                fn(event)

    def _sample_decoded(self, logits: jnp.ndarray, routed=None):
        """The decode step's next token per slot, on the host, and the
        held experts each slot's token chose ([slots, expert layers *
        held], or None), both in the tokens' one copy."""
        with TraceAnnotation(SAMPLE_SPAN):
            tok = self._sample(logits[:, -1])
            if routed is not None:
                tok = _with_routed(tok, routed)
            with TraceAnnotation(SYNC_SPAN):
                host = np.asarray(tok)
        if routed is None:
            return host, None
        return host[:, 0], host[:, 1:]

    def _count_routed(self, event: dict, active: List[int], routed) -> None:
        """Record in the step's event (and the metrics) the tokens routed
        to held experts and the (layer, held expert) pairs they hit, over
        the active slots."""
        if routed is None:
            return
        hits = routed[active]
        event["moe_routed"] = int(hits.sum())
        event["moe_experts_hit"] = int(hits.any(axis=0).sum())
        if self.metrics is not None:
            self.metrics.counter("serve_moe_routed_tokens").inc(
                event["moe_routed"])
            self.metrics.counter("serve_moe_experts_hit").inc(
                event["moe_experts_hit"])

    # ------------------------------------------------------------------ #
    def step(self):
        """One engine step.

        Legacy mode: whole-prompt prefill at admission + one batched
        decode across the active slots.  In-flight mode
        (``prefill_chunk_tokens=``): prefill chunks and the batched decode
        run as ONE merged step (a single ``{"kind": "step"}`` event — the
        backend schedules both phases through one merged Program).
        """
        if self.prefill_chunk_tokens is not None:
            return self._step_inflight()
        return self._step_legacy()

    def _decode_step(self, active: List[int]):
        """Run the batched decode over ``active`` slot indices; returns
        the step logits (sampling happens after observers fire) and, for
        a model with held experts, the experts each slot chose."""
        with TraceAnnotation(DECODE_SPAN):
            tokens = np.zeros((self.max_slots,), np.int32)
            pos = np.zeros((self.max_slots,), np.int32)
            for i in active:
                slot = self.slots[i]
                tokens[i] = slot.request.output[-1]
                pos[i] = slot.pos
            cache = self.cache
            logits, self.cache, *routed = self._decode(
                self.params, jnp.asarray(tokens), cache, jnp.asarray(pos))
            self._count_in_place(cache, batch=True)
        self.decode_batch_sizes.append(len(active))
        self.step_log.append({"phase": "decode", "tokens": len(active),
                              "slots": len(self._active())})
        if self.metrics is not None:
            self.metrics.counter("serve_decode_steps").inc()
            self.metrics.counter("serve_decode_tokens").inc(len(active))
            self.metrics.histogram("serve_batch_size").observe(len(active))
            self.metrics.gauge("serve_slot_occupancy").set(
                len(self._active()) / self.max_slots)
        return logits, (routed[0] if routed else None)

    def _finish_decoded(self, active: List[int], next_tok) -> None:
        """Append sampled tokens and retire finished slots — EOS and
        token-budget completions, plus window truncations
        (``Request.truncated``) when ``slot.pos`` hits the cache edge."""
        with TraceAnnotation(FINISH_SPAN):
            for i in active:
                slot = self.slots[i]
                req = slot.request
                req.output.append(int(next_tok[i]))
                slot.pos += 1
                hit_eos = req.eos_id is not None and next_tok[i] == req.eos_id
                full = slot.pos >= self.max_seq - 1
                if len(req.output) >= req.max_new_tokens or hit_eos or full:
                    req.done = True
                    req.truncated = (full and not hit_eos
                                     and len(req.output) < req.max_new_tokens)
                    self.finished.append(req)
                    self._page_release(req)
                    slot.request = None
                    slot.pos = 0

    def _step_legacy(self):
        """One batched decode step across all active slots."""
        self._admit()
        active = self._ensure_kv(self._active())
        if not active:
            return False
        logits, routed = self._decode_step(active)
        event = {"kind": "decode", "tokens": 1,
                 "uids": [self.slots[i].request.uid for i in active],
                 "positions": [int(self.slots[i].pos) for i in active]}
        self._notify(event)
        next_tok, routed = self._sample_decoded(logits, routed)
        self._count_routed(event, active, routed)
        self._finish_decoded(active, next_tok)
        return True

    # ------------------------------------------------------------------ #
    # In-flight batching: prefill chunks + decode in one merged step
    # ------------------------------------------------------------------ #
    def _admit_inflight(self):
        """Assign free slots to queued requests (admission-gated) without
        running any prefill — chunks advance inside the merged step.
        Paged engines pin the whole (re-)prefill's pages up front."""
        with TraceAnnotation(ADMIT_SPAN):
            for slot in self.slots:
                if slot.request is not None:
                    continue
                req = self._next_admittable()
                if req is None:
                    return
                tokens = self._resume_tokens(req)
                if not self._page_admit(req, len(tokens)):
                    return
                slot.request = req
                slot.pos = 0
                slot.filled = 0
                slot.tokens = tokens
                slot.staging = self.api.init_cache(1, self.max_seq)

    def _advance_chunks(self) -> List[dict]:
        """Advance every prefilling slot by one chunk, oldest slot first,
        until the step's ``prefill_chunk_tokens`` budget is spent."""
        budget = self.prefill_chunk_tokens
        chunks: List[dict] = []
        with TraceAnnotation(CHUNKS_SPAN):
            for i, slot in enumerate(self.slots):
                if budget <= 0:
                    break
                if slot.request is None or slot.staging is None:
                    continue
                chunk = self._advance_chunk(i, slot, budget)
                budget -= chunk["tokens"]
                chunks.append(chunk)
        return chunks

    def _advance_chunk(self, i: int, slot: _Slot, budget: int) -> dict:
        """Prefill slot ``i``'s next chunk of at most ``budget`` tokens; the
        prompt's last chunk samples its first token and lands the staged
        lane in the batch cache.  Returns the chunk's step-event entry."""
        req = slot.request
        with TraceAnnotation(PREFILL_CHUNK_SPAN, uid=req.uid):
            fill = slot.tokens if slot.tokens is not None else req.prompt
            plen = len(fill)
            c = min(budget, plen - slot.filled)
            pos0 = slot.filled
            toks = jnp.asarray(fill[None, pos0:pos0 + c])
            staging = slot.staging
            logits, slot.staging = self._prefill_chunk(
                self.params, toks, staging, pos0)
            self._count_in_place(staging)
            slot.filled += c
            self.step_log.append({"phase": "prefill_chunk", "uid": req.uid,
                                  "tokens": c,
                                  "slots": len(self._active())})
            if self.metrics is not None:
                self.metrics.counter("serve_prefill_chunks").inc()
                self.metrics.counter("serve_prefill_tokens").inc(c)
            last = slot.filled >= plen
            done = False
            if last:
                if req.output:
                    # resuming after preemption: the re-prefill's sample
                    # duplicates output[-1] (same cache prefix) — discard
                    # it and rejoin the decode loop mid-stream
                    pass
                else:
                    tok = self._sample(logits[:, -1])
                    done = self._first_token(req, int(tok[0]), plen)
                if done:
                    self._page_release(req)
                    slot.request = None
                else:
                    # decode-ready: land the staged lane in the batch cache
                    self._land(i, slot.staging, req.uid, plen)
                    slot.pos = plen
                slot.staging = None
                slot.filled = 0
                slot.tokens = None
                self._log_prefill(req, plen, count_tokens=False)
            return {"uid": req.uid, "tokens": c, "pos0": pos0,
                    "last": last, "done": done}

    def _step_inflight(self):
        """One in-flight step: admit, advance prefill chunks under the
        token budget, batch-decode the decode-ready slots, and emit a
        single merged ``step`` event covering both phases."""
        self._admit_inflight()
        chunks = self._advance_chunks()
        active = self._ensure_kv([i for i in self._active()
                                  if self.slots[i].staging is None])
        if not chunks and not active:
            return False
        if active:
            logits, routed = self._decode_step(active)
        event = {
            "kind": "step", "chunks": chunks, "tokens": 1,
            "uids": [self.slots[i].request.uid for i in active],
            "positions": [int(self.slots[i].pos) for i in active],
        }
        self._notify(event)
        if active:
            next_tok, routed = self._sample_decoded(logits, routed)
            self._count_routed(event, active, routed)
            self._finish_decoded(active, next_tok)
        return True

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.queue or self._active()) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished


@jax.jit
def _with_routed(tok, routed):
    """Sampled tokens [B] and each slot's held-expert choices [B, L, E] as
    one int32 array [B, 1 + L * E], so that one copy brings both."""
    flat = routed.reshape(routed.shape[0], -1).astype(jnp.int32)
    return jnp.concatenate([tok[:, None], flat], axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(cache, single_cache, slot):
    """Copy a 1-lane prefilled cache into lane ``slot`` of the engine cache,
    which is donated and updated in place; ``slot`` is traced, so one
    program serves every lane.

    Works for KVCache / SSMCache / HybridCache: every leaf's batch axis is
    the second dim for stacked [L, B, ...] leaves.
    """
    def write(full, single):
        return jax.lax.dynamic_update_slice_in_dim(full, single, slot,
                                                   axis=1)
    return jax.tree.map(write, cache, single_cache)
