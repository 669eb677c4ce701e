"""DecodeServer: continuous batching over the slot pool.

The online counterpart of the batch-oriented eval path (PR 2): a
persistent server object that compiles its program set once, keeps all
state device-resident (TensorFlow-paper serving/training split), and
multiplexes S concurrent requests through ONE jitted decode dispatch.

The loop, per ``step()``:

1. **sweep** — an in-flight request past its deadline, or canceled,
   leaves its slot (its ``remaining`` on the device goes to zero).
2. **admit** — pop queued requests into free slots; each admission
   DISPATCHES one program (``engine.admit``, the ``serve.prefill`` span:
   the bucket-compiled prefill and the slot's loop state, the request's
   key made from its seed inside it) and books the request in its slot
   with its first token PENDING on the device: nothing here waits for
   the chip. Admission happens ONLY here.
   A model with learned sparse attention is prefilled in blocks
   (``engine.prefill_blocks``), and its admission is spread over steps:
   a request takes its slot at once, ONE block of one prompt runs a step
   (``serve.prefill_block``; the prompts part-way in take turns), and
   the last block is the request's ``serve.prefill`` (``_admit_blocks``),
   its loop state written behind it (``engine.admit_slot``).
   The live slots decode between the blocks.
3. **decode** — if any slot is owed a token (a pending first token counts
   as the one token it is), run ONE decode dispatch,
   of the one kind of block the model has: the plain single-step program
   (the PR-10 step), or, for a model with a multi-token-prediction module
   (``TransformerLM(mtp=)``), one speculative round drafted from it.
4. **read** — the token block dispatched a step earlier reaches the host,
   then the first tokens of this step's admissions
   (``serve.first_token``): their prefills ran before the block just
   dispatched, so the read returns when a read before the dispatch would
   have — ``first_token_s`` and TTFT are what they were, in the step
   that admitted — and the chip runs that block while the host books
   them; a request with ``max_new_tokens == 1`` retires here.
5. **emit** — every slot that was live in the block read appends its
   tokens; finished requests retire and free their slots.

The loop's state — per slot the cursor, the last token, the tokens still
owed and the RNG key — lives ON THE DEVICE (``SlotKVCache.loop``): each
decode program takes it and returns it advanced, and the host writes it
only where a request enters or leaves a slot. A request ends on
``max_new_tokens`` alone, and the device freezes a slot that owes
nothing more (``remaining``), so nothing a decode program computes waits
for the host to have read a token. Either kind of block therefore runs
ONE DISPATCH AHEAD of the host: step 3 dispatches block *n + 1* from
the device's state before step 4 reads block *n*, the one dispatched a
step earlier (``_unread``), and the chip computes *n + 1* while the host
books *n*. What the host chooses without that read is the live set of
*n + 1* (``_owed``): the slots that MAY still owe a token once block *n*
is read, counting for it the one token it holds for a live slot at least
(exact for a plain step; a round yields one token or two). With rounds
the live set is thus a superset of the truth by the slots that finished
on an accepted draft:
the device gives those a count of 0, and a dispatch in which every row
comes back 0 (a lone request's tail) is counted, ``stats()
["empty_dispatches"]``. Reading behind costs a slot nothing: a slot is
free the moment the block that is CERTAIN to end its request is
dispatched (``_vacate``: it owes one token, which the block holds),
the next step's admission takes it, and the
request, off the slot table, books its last tokens and retires when that
block is read. Only a request that ends on an accepted draft, which the
host cannot foresee, holds its slot one round longer. With nothing
unread (the first step after the server was empty) the order is
dispatch, then read in the next step; ``busy()`` stays true while a
block is unread, and ``drain()``, ``stats()``, ``flush()`` and the
fleet's drain-time export read it first.

The host sees one token-block readback per dispatch ([S] of a plain
step, [1, S, 4] of a round; a model with routed experts
sends its routing in the same read, carried with ITS block) — that is
the decode loop's entire host/device chatter: between two decode steps
with no admission nothing travels host → device, and an admission sends
its padded prompt and one int32 vector with its one program's call.
Everything else (queue, slot table) is host bookkeeping the scheduler
needs anyway.

Observability: queue depth / occupancy gauges, token + dispatch
counters (``serve_decode_steps_total`` counts DISPATCHES — a round
holds up to two tokens a slot; ``stats()`` derives dispatches/token and
tokens/dispatch), the pool read's key-block counters
(``serve_decode_kv_blocks_total`` against
``serve_decode_kv_blocks_pool_total``: what the live slots' keys cost
of what every slot's cursor would), speculative proposed/accepted
counters, TTFT/TPOT/latency histograms (``monitor/registry``), and the
spans below (``monitor/trace`` — forwarded to the flight recorder when
one is live and, like every span, a ``dl4j.<name>`` event on the device
trace's clock in any ``jax.profiler`` trace taken while the server
runs):

- ``serve.step`` (``live``, ``admitted``) — one scheduler iteration;
  parent of the four phases:
- ``serve.admit`` (``n``) — all admissions of the step, dispatched; opened
  only when a slot is free and a request or hand-off is waiting. Holds,
  per request, ``serve.queued`` (``request``, ``criticality``:
  ``submit_s`` → popped from the queue; recorded when it ends) and
  ``serve.prefill`` (``request``, ``slot``, ``prompt_len``, ``bucket``,
  ``queue_wait_us``: pad and the dispatch of the admission's one
  program), or ``serve.handoff.install``. Learned sparse
  attention: ``serve.prefill`` is the prompt's last block (``blocks``:
  how many it had) and each block before it a ``serve.prefill_block``
  (``request``, ``slot``, ``block``, ``blocks``) in an earlier step's
  ``serve.admit``.
- ``serve.first_token`` (``request``, ``slot``, ``ahead`` = 1 when a
  decode block was dispatched behind the prefill) — after the step's
  ``serve.decode``, one a request admitted in the step: the first
  token's read-back, up to ``first_token_s``, and its booking. A
  prompt's ``experts_touched`` and ``experts_read`` land HERE, never on
  ``serve.decode``; routed experts on a share: a ``serve.passes``
  (``moe_rows_run``, ``moe_pairs_run``) inside the span that read a
  routing whose sorted form ran in passes (``_read_block``).
- ``serve.decode`` (``live``, ``kind`` = ``plain`` | ``spec``,
  ``ahead`` = 1 when the dispatch was issued while the
  previous block was unread; ``kv_blocks``, ``kv_blocks_pool``: the key
  blocks a layer the pool kernel fetches for the slots dispatched, and
  what a read of every slot's cursor, live or frozen, would fetch —
  counted from the host's slot table, ``_book_kv_blocks``, as are
  ``kv_rows``, the K/V rows the live slots hold up to their cursors over the
  'attn' layers (for a model that gives a window a layer also apart:
  ``kv_rows_window``, ``min(c + 1, window)`` a layer with a window, and
  ``kv_rows_full``, ``c + 1`` a layer without; ``kv_blocks`` are then over
  all the 'attn' layers, each pool's blocks times its layers), and, for a
  model with 'kda', 'gdn' or 'ret' layers,
  ``state_slots``, the slots whose recurrent state the step moves (beside
  ``kv_rows`` 0 where no layer keeps rows a position); for a
  model with learned sparse attention ``keys_cached``, ``keys_attended`` and
  ``rows_gathered`` instead, and with pooled index keys ``pools_scored``,
  ``pools_selected`` and ``tail_attended`` beside them) — the decode
  dispatch (``live`` slots; 0: none may be owed a token), then the
  read-back of the block dispatched a step earlier, whatever its kind.
  ``experts_touched`` and ``experts_read``, and a round's ``rounds``,
  ``proposed``, ``accepted`` and ``emitted`` (the device's counts), are
  of the block READ.
- ``serve.emit`` (``tokens``, ``retired``) — after the reads: the
  per-slot token loop over the block read, histograms, retirement.
- ``serve.request`` (``request``, ``tokens``, ``slot``) — ``submit_s`` →
  ``finish_s``, recorded at retirement; ``request`` is
  ``ServeRequest.id`` on every span of one request.

Sampling runs inside the prefill and decode programs and is no host
phase. ``serve.queued`` and ``serve.request`` carry the SERVER clock's
times; that is the tracer's clock unless one of the two was injected.
"""

from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import Callable, Deque, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.models.dsa import live_slots, selection_width
from deeplearning4j_tpu.monitor import metrics, tracer
from deeplearning4j_tpu.pallas.decode_attention import (
    key_block_span, pool_block_rows)
from deeplearning4j_tpu.serving.engine import (
    DecodeEngine, prefill_block_count, seed_key, unpack_routing)
from deeplearning4j_tpu.serving.kv_cache import attn_places
from deeplearning4j_tpu.serving.scheduler import (
    AdmissionVerdict, RequestQueue, ServeQueueFull, ServeRequest,
    criticality_rank, serve_deadline_s, serve_max_queue, serve_slots)

__all__ = ["DecodeServer"]

# histogram buckets tuned for online latency (the default registry
# ladder tops out too coarse below 10 ms)
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                    float("inf"))


class DecodeServer:
    """Slot-batched online decode server for a :class:`TransformerLM`."""

    def __init__(self, model, *, slots: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 max_len: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 fuse_steps: Optional[int] = None,
                 kv_dtype: Optional[str] = None, mesh=None,
                 clock=time.monotonic, record_routing: bool = False,
                 ring: bool = True):
        # the drivers under benchmarks/ pass fuse_steps=1 (ROADMAP D12)
        if fuse_steps not in (None, 1):
            raise ValueError(
                f"fuse_steps={fuse_steps}: a dispatch is one decode step "
                "(or one round of a model's own module). K fused steps "
                "bought the host's turn between two dispatches, which "
                "reading a block one dispatch behind already hides, and "
                "made an arriving request wait K steps for its slot "
                "(ISSUE 43)")
        if mesh is None:
            from deeplearning4j_tpu.parallel.sharding_registry import (
                mesh_from_env)

            mesh = mesh_from_env()
        self.engine = DecodeEngine(
            model, slots if slots is not None else serve_slots(),
            max_len=max_len, temperature=temperature, top_k=top_k,
            buckets=buckets, kv_dtype=kv_dtype, mesh=mesh, ring=ring)
        self.model = model
        self.slots = self.engine.slots
        self.max_len = self.engine.max_len
        self.queue = RequestQueue(
            max_queue if max_queue is not None else serve_max_queue())
        self.clock = clock
        self._slot_req: List[Optional[ServeRequest]] = [None] * self.slots
        # the host's copy of the device's cursors (written where the
        # device's are: admission, release, a dispatch's own advance) and
        # what ``kv_rows`` and ``kv_blocks`` are counted from: the 'attn'
        # layers in groups that read alike, ``(layers, window, positions
        # the pool holds a slot, hkv, is it a ring, the pool kernel's rows a
        # key block)`` — the last None where the pool has no kernel read (a
        # mesh, a head size off the lanes). One group, unless the model
        # gives a window a layer.
        self._cursors = np.zeros(self.slots, np.int64)
        pool = self.engine.cache
        groups: dict = {}
        for (name, _), i in zip(attn_places(model, pool.ring is not None),
                                model.layers_of("attn")):
            dims = pool.ring_dims if name == "ring" else pool.pool_dims
            key = (model.windows[i], dims[2], dims[3], name == "ring",
                   None if mesh is not None
                   else pool_block_rows(dims, pool.kv_dtype))
            groups[key] = groups.get(key, 0) + 1
        self._kv_reads = [(n,) + key for key, n in groups.items()]
        self.kv_blocks = 0
        self.kv_blocks_pool = 0
        self.kv_rows = 0
        # of ``kv_rows``, the layers' with a window and the others' (a model
        # that gives a window a layer)
        self.kv_rows_window = 0
        self.kv_rows_full = 0
        self.state_slots = 0
        self._recurrent = bool(model.kda or model.gdn or model.ret)
        # learned sparse attention: latent rows the dispatched slots held
        # below their cursors, and rows their queries attended (at most
        # ``topk`` each), summed over decode steps, live slots and 'mla'
        # layers; and the blocks the prefill programs ran
        self.keys_cached = 0
        self.keys_attended = 0
        self.rows_gathered = 0
        self.prefill_blocks = 0
        # pooled index keys (dsa["pool"]): pools the decode steps' queries
        # scored and selected, tail positions they attended unscored; prefill
        # blocks that continued a recurrence ('kda' layers, block > 0)
        self.pools_scored = 0
        self.pools_selected = 0
        self.tail_attended = 0
        self.recurrence_blocks = 0
        # {slot: [engine.prefill_blocks generator, its blocks, queue wait in
        # us, blocks run]} of the requests whose prompt is part-way into
        # its slot, the next to run first (``_admit_blocks``)
        self._prefilling: "OrderedDict[int, list]" = OrderedDict()
        self._last_tok_s = np.zeros(self.slots, np.float64)
        # the dispatched block the host has not read: ``(tokens, routing,
        # {slot: request}, {slot: last token's instant})`` — device arrays,
        # the slots live in it and those of them freed at the dispatch
        self._unread: Optional[Tuple[object, object, dict, dict]] = None
        # the admissions of the step in hand whose first token is still on
        # the device: ``(request, token, routing)``, booked in their slots
        # already; read behind the step's decode dispatch
        # (``_read_first_tokens``), so none outlives its step
        self._pending: List[Tuple[ServeRequest, object, object]] = []
        # externally-prefilled requests waiting for a free slot: each
        # entry carries an ``install(engine, slot) -> (last_tok, cursor,
        # key)`` that lands the handed-off KV slab into the slot
        # (serving/fleet/handoff.py builds these)
        self._handoffs: Deque[Tuple[ServeRequest, Callable]] = deque()
        self.finished: List[ServeRequest] = []
        # overload-control ledger: every shed request + the decision
        # evidence behind it (mirrored to the serve.shed tracer event)
        self.shed: List[ServeRequest] = []
        self.shed_log: List[dict] = []
        self.shed_by_class: dict = {}
        self.expired_in_queue = 0
        self.expired_in_flight = 0
        self.steps = 0
        self.decode_ahead = 0
        # admissions whose first token was read with a decode block already
        # dispatched behind their prefill
        self.admit_ahead = 0
        # dispatches whose block gave no slot a token, by the device's
        # own counts: what dispatching on "may owe" costs (rounds only)
        self.empty_dispatches = 0
        self.decode_tokens = 0
        self.slot_dispatches = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # rounds the live slots took and tokens those rounds yielded, as the
        # device counted them (a request's last round may yield a token
        # more than it is owed)
        self.spec_rounds = 0
        self.spec_emitted = 0
        # routed experts: (token, expert) pairs every expert held here, of
        # every layer that has experts, has received from live rows,
        # prefills and plain decode steps alike ([Lmoe, held]; an empty
        # array for a dense model); the live rows those layers saw; and of
        # the decode blocks read, how many there were and the (layer,
        # expert) cells they reached and their programs fetched; and of
        # the layers that took the sorted form, the sorted rows their
        # passes ran and the pairs held here those passes were for
        self.moe_expert_load = np.zeros(
            (model.n_layers("moe"), model.experts_held), np.int64)
        self.moe_rows = 0
        self.moe_rows_run = 0
        self.moe_pairs_run = 0
        self.moe_decode_blocks = 0
        self.moe_decode_touched = 0
        self.moe_decode_read = 0
        self._decode_kind = "spec" if self.engine.spec else "plain"
        # record_routing: every request keeps the experts that served
        # each of its positions, and their weights, as the prefill and
        # decode programs chose them (``ServeRequest.routing``): what a
        # check against a reference needs, since activations rounded to
        # bf16 flip near-ties of the router. They reach the host with
        # the load whatever this says (one array, 9 KiB a step at 32
        # slots, 4 layers, 8 of 64 experts a token); this keeps them.
        # A model that drafts from its own module (``mtp=``) records its
        # rounds too: the rows of the positions a round made permanent, the
        # module's layer last, and every draft a round verified with the
        # position it was proposed for (``ServeRequest.drafts``).
        self.record_routing = bool(record_routing)
        if self.record_routing and not model.num_experts:
            raise ValueError(
                "record_routing needs a model with routed experts")
        self._reg = metrics()

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, seed: int = 0,
               deadline_s: Optional[float] = None,
               criticality: str = "interactive") -> ServeRequest:
        """Enqueue one request. Validates against the slot capacity the
        way ``generate`` validates against its cache size; raises
        :class:`~.scheduler.ServeQueueFull` at the queue bound."""
        verdict = self.try_submit(prompt, max_new_tokens, seed=seed,
                                  deadline_s=deadline_s,
                                  criticality=criticality)
        if not verdict.admitted:
            raise ServeQueueFull(
                f"serve queue at max depth {self.queue.max_depth}")
        return verdict.request

    def try_submit(self, prompt, max_new_tokens: int, *,
                   seed: int = 0,
                   deadline_s: Optional[float] = None,
                   criticality: str = "interactive",
                   displace: bool = True) -> AdmissionVerdict:
        """Non-blocking ``submit``: returns an
        :class:`~.scheduler.AdmissionVerdict` instead of raising at the
        queue bound, so a routing frontend can place across replicas
        without exception-driven control flow. Malformed requests
        (empty prompt, capacity overflow, unknown criticality) still
        raise — those are caller bugs, not load conditions.

        ``deadline_s`` is the ABSOLUTE expiry instant on this server's
        clock (None falls back to ``DL4J_SERVE_DEADLINE_S`` as a budget
        from now); an already-expired submit is shed on the spot
        (reason ``"expired"``). At the queue bound, ``displace=True``
        lets this arrival shed the costliest queued request of a
        strictly lower criticality class (the victim rides back on the
        verdict's ``displaced`` field); the router's first placement
        pass disables it so plain spill is tried fleet-wide before
        anything is shed."""
        criticality_rank(criticality)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = int(prompt.shape[0]) + max_new_tokens
        # speculative verify writes up to spec_tokens candidate K/V past
        # the live cursor, so the slot needs that slack in the pool
        slack = self.engine.spec_tokens if self.engine.spec else 0
        if total + slack > self.max_len:
            raise ValueError(
                f"prompt_len + max_new_tokens = {total}"
                + (f" (+ {slack} speculative slack)" if slack else "")
                + f" exceeds the server's slot capacity "
                f"max_len={self.max_len}")
        now = self.clock()
        if deadline_s is None:
            budget = serve_deadline_s()
            deadline_s = None if budget is None else now + budget
        req = ServeRequest(prompt=prompt, max_new_tokens=max_new_tokens,
                           seed=seed, deadline_s=deadline_s,
                           criticality=criticality)
        req.submit_s = now
        if req.expired(now):
            # a deadline already in the past: shed at the earliest
            # possible point — before it ever costs a queue entry
            self._shed(req, where="admission", reason="deadline", now=now)
            self._reg.counter("serve_requests_total").inc(event="rejected")
            return AdmissionVerdict(admitted=False, reason="expired",
                                    queue_depth=len(self.queue))
        if not self.queue.try_push(req):
            victim = None
            if displace:
                admitted, victim = self.queue.displace(req)
            else:
                admitted = False
            if not admitted:
                self._reg.counter("serve_requests_total").inc(
                    event="rejected")
                return AdmissionVerdict(admitted=False,
                                        reason="queue_full",
                                        queue_depth=len(self.queue))
            if victim is not None:
                self._shed(victim, where="queue", reason="shed_overload",
                           now=now, displaced_by=req.id)
            self._reg.counter("serve_requests_total").inc(
                event="submitted")
            self._reg.gauge("serve_queue_depth").set(len(self.queue))
            return AdmissionVerdict(admitted=True, request=req,
                                    queue_depth=len(self.queue),
                                    displaced=victim)
        self._reg.counter("serve_requests_total").inc(event="submitted")
        self._reg.gauge("serve_queue_depth").set(len(self.queue))
        return AdmissionVerdict(admitted=True, request=req,
                                queue_depth=len(self.queue))

    def _shed(self, req: ServeRequest, *, where: str, reason: str,
              now: float, displaced_by: Optional[int] = None) -> None:
        """Shed one request with its evidence: state flips to ``shed``,
        the decision lands in ``shed_log`` AND on the tracer timeline
        (``serve.shed`` event → flight recorder), and the
        ``serve_shed_total`` counter / ``serve_shed_by_class`` gauge
        move — nothing is dropped silently."""
        req.state = "shed"
        req.shed_reason = reason
        req.finish_s = now    # when it was shed (drop-series timestamp)
        self.shed.append(req)
        self.shed_by_class[req.criticality] = (
            self.shed_by_class.get(req.criticality, 0) + 1)
        if where == "queue" and reason == "deadline":
            self.expired_in_queue += 1
        elif where == "in_flight":
            self.expired_in_flight += 1
        decision = {"request": req.id, "criticality": req.criticality,
                    "where": where, "reason": reason, "t": now}
        if displaced_by is not None:
            decision["displaced_by"] = displaced_by
        self.shed_log.append(decision)
        self._reg.counter("serve_shed_total").inc(
            criticality=req.criticality, where=where)
        self._reg.gauge("serve_shed_by_class").set(
            float(self.shed_by_class[req.criticality]),
            criticality=req.criticality)
        tracer().event("serve.shed", **decision)

    def admit_external(self, req: ServeRequest,
                       install: Callable) -> None:
        """Queue an externally-prefilled request (prefill/decode split):
        at the next step boundary a free slot is claimed and
        ``install(engine, slot) -> (last_token, cursor, rng_key)`` lands
        the handed-off KV slab into it — the request then decodes exactly
        like a locally-prefilled one. ``req`` must already carry
        its first token (the prefill replica sampled it); its TTFT was
        recorded at prefill time, so this path never re-observes it."""
        if self.engine.spec:
            raise ValueError(
                "handoff into a speculative decode server is "
                "unsupported: a hand-off carries no draft for the "
                "slot's first round")
        if not req.tokens:
            raise ValueError(
                "admit_external needs a prefilled request (its first "
                "token sampled by the prefill replica)")
        self._handoffs.append((req, install))

    def handoff_headroom(self) -> int:
        """Free slots not yet spoken for by queued handoffs — the
        router's can-this-replica-take-a-slab signal."""
        return self.free_slot_count() - len(self._handoffs)

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [s for s, r in enumerate(self._slot_req) if r is None]

    def _live_slots(self) -> List[int]:
        return [s for s, r in enumerate(self._slot_req) if r is not None]

    def free_slot_count(self) -> int:
        """How many slots the next step boundary can admit into — the
        router's least-loaded placement signal."""
        return len(self._free_slots())

    def occupancy(self) -> float:
        return len(self._live_slots()) / self.slots

    def busy(self) -> bool:
        return (bool(self._live_slots()) or len(self.queue) > 0
                or bool(self._handoffs) or self._unread is not None)

    def _admit_handoff(self, slot: int) -> None:
        req, install = self._handoffs.popleft()
        with tracer().span("serve.handoff.install", request=req.id,
                           slot=slot):
            last_tok, cursor, key = install(self.engine, slot)
            self.engine.admit_slot(slot, last_tok, cursor,
                                   req.max_new_tokens - len(req.tokens), key)
            self._cursors[slot] = cursor
        now = self.clock()
        req.state = "running"
        req.handoff = True
        req.slot = slot
        self._slot_req[slot] = req
        self._last_tok_s[slot] = now
        # TTFT was recorded by the prefill replica; the installed slab
        # already covers every emitted token, so a request that arrived
        # complete just retires
        if len(req.tokens) >= req.max_new_tokens:
            self._retire(slot, req, now)

    def _admit(self) -> int:
        free = self._free_slots()
        if not self._prefilling and (
                not free or not (self._handoffs or len(self.queue))):
            return 0
        with tracer().span("serve.admit") as sp:
            sp.attrs["n"] = admitted = (
                self._admit_blocks(free) if self.engine.block_prefill
                else self._admit_into(free))
        return admitted

    def _pop_request(self):
        """The next queued request that is still wanted, its wait recorded
        (``serve.queued``), with that wait in microseconds: ``(request,
        waited)``, or None with an empty queue. Pops past corpses: an
        expired request sheds HERE — before its prefill burns the slot —
        and a canceled hedge loser vanishes without a trace in the
        finished ledger."""
        req = self.queue.pop()
        while req is not None:
            now = self.clock()
            if req.canceled:
                req.state = "canceled"
            elif req.expired(now):
                self._shed(req, where="queue", reason="deadline", now=now)
            else:
                tracer().record("serve.queued", req.submit_s, now,
                                request=req.id, criticality=req.criticality)
                return req, int(1e6 * (now - req.submit_s))
            req = self.queue.pop()
        return None

    def _admit_into(self, free: List[int]) -> int:
        admitted = 0
        for slot in free:
            # handed-off slabs first: their prefill compute is already
            # spent — a queued prompt admitted ahead of them would idle
            # a finished prefill while burning a slot on new work
            if self._handoffs:
                self._admit_handoff(slot)
                admitted += 1
                continue
            popped = self._pop_request()
            if popped is None:
                break
            req, waited = popped
            prompt_len = int(req.prompt.shape[0])
            with tracer().span(
                    "serve.prefill", request=req.id, slot=slot,
                    prompt_len=prompt_len,
                    bucket=self.engine.prompt_bucket(prompt_len),
                    queue_wait_us=waited):
                # one program: the prefill and the slot's loop state
                self._await_first_token(req, slot, *self.engine.admit(
                    req.prompt, slot, req.max_new_tokens, req.seed))
            admitted += 1
        return admitted

    def _admit_blocks(self, free: List[int]) -> int:
        """Admission for a model with learned sparse attention or one that
        drafts from its own module, whose
        prefill is a row of block programs (``engine.prefill_blocks``):
        every free slot takes a queued request, and then ONE block runs —
        of the request that has waited longest for one — before the step's
        decode dispatch, so a 28k-token prompt holds the live slots back a
        block at a time and not for all of its fourteen, and a short prompt
        behind it shares the chip with it instead of waiting it out.
        Returns how many requests' last block ran."""
        for slot in free:
            popped = self._pop_request()
            if popped is None:
                break
            req, waited = popped
            prompt_len = int(req.prompt.shape[0])
            blocks = prefill_block_count(
                prompt_len, self.engine.prompt_bucket(prompt_len))
            self.prefill_blocks += blocks
            if self.model.kda:  # every block but the first continues one
                self.recurrence_blocks += blocks - 1
            req.state, req.slot = "running", slot
            self._slot_req[slot] = req
            self._prefilling[slot] = [
                self.engine.prefill_blocks(
                    req.prompt, slot, seed_key(req.seed)),
                blocks, waited, 0]
        if not self._prefilling:
            return 0
        slot, state = next(iter(self._prefilling.items()))
        run, blocks, waited, done = state
        req = self._slot_req[slot]
        if done + 1 < blocks:
            with tracer().span("serve.prefill_block", request=req.id,
                               slot=slot, block=done, blocks=blocks):
                next(run)
            state[3] += 1
            self._prefilling.move_to_end(slot)
            return 0
        # the prompt's last block is the request's ``serve.prefill``
        prompt_len = int(req.prompt.shape[0])
        del self._prefilling[slot]
        with tracer().span(
                "serve.prefill", request=req.id, slot=slot,
                prompt_len=prompt_len,
                bucket=self.engine.prompt_bucket(prompt_len),
                queue_wait_us=waited, blocks=blocks):
            tok, key, routing = next(run)
            # the slot's loop state straight from the program's outputs
            self.engine.admit_slot(slot, tok, prompt_len,
                                   req.max_new_tokens - 1, key)
            self._await_first_token(req, slot, tok, routing)
        run.close()
        return 1

    def _await_first_token(self, req: ServeRequest, slot: int, tok,
                           routing) -> None:
        """A prefill is dispatched and the slot's loop state queued behind
        it, inside the request's ``serve.prefill`` span: the request holds
        ``slot`` from here on, its first token PENDING on the device
        (``_owed`` counts it) until the step has dispatched its decode block
        (``_read_first_tokens``). Nothing here waits for the device."""
        req.state = "running"
        req.slot = slot
        self._slot_req[slot] = req
        self._cursors[slot] = int(req.prompt.shape[0])
        self._pending.append((req, tok, routing))

    def _read_first_tokens(self, ahead: bool) -> None:
        """The first tokens of the step's admissions reach the host, behind
        the step's decode dispatch (``ahead``: a block was dispatched, so
        the chip runs it while the host books these): a ``serve.first_token``
        span a request, and in it the read (``_read_block``: the routing
        the token came with is booked, on this span), ``first_token_s``,
        the routing and selection where they are recorded, TTFT, and
        retirement where one token was all it asked for."""
        pending, self._pending = self._pending, []
        for req, tok, routing in pending:
            slot = req.slot
            with tracer().span("serve.first_token", request=req.id,
                               slot=slot, ahead=int(ahead)):
                prompt_len = int(req.prompt.shape[0])
                tok, rows, selection = self._read_block(tok, routing,
                                                        prompt_len)
                req.first_token_s = self.clock()
                if self.record_routing:
                    req.routing = [tuple(a[:, :prompt_len].copy()
                                         for a in rows)]
                    if selection is not None:   # the prompt's last position
                        req.selection = [selection[:, None]]
                    if self.model.mtp:
                        req.drafts = []
                req.tokens.append(int(tok))
                self._last_tok_s[slot] = req.first_token_s
                if req.ttft_s is not None:
                    self._reg.histogram("serve_ttft_seconds",
                                        buckets=_LATENCY_BUCKETS
                                        ).observe(req.ttft_s)
                self._reg.counter("serve_tokens_total").inc()
                if len(req.tokens) >= req.max_new_tokens:
                    self._retire(slot, req, req.first_token_s)
        if ahead:
            self.admit_ahead += len(pending)
            self._reg.counter("serve_admit_ahead_total").inc(len(pending))

    def _retire(self, slot: int, req: ServeRequest, now: float) -> None:
        req.state = "finished"
        req.finish_s = now
        if self._slot_req[slot] is req:     # else it left at its dispatch
            self._slot_req[slot] = None
        self.finished.append(req)
        self._reg.counter("serve_requests_total").inc(event="finished")
        if req.latency_s is not None:
            self._reg.histogram("serve_request_latency_seconds",
                                buckets=_LATENCY_BUCKETS
                                ).observe(req.latency_s)
            tracer().record("serve.request", req.submit_s, now,
                            request=req.id, tokens=len(req.tokens),
                            slot=slot)

    def _owed(self) -> dict:
        """``{slot: request}`` of the slots that may be owed a token no
        dispatched block holds yet: the live set of the next dispatch,
        known without reading a token because a request ends on
        ``max_new_tokens`` alone (the device's ``remaining > 0``). What
        the host has counted a request owed, less the one token the unread
        block holds for it at least, or the first token it has pending
        (``_pending``: a request of one token is not live): exact
        for plain steps; with rounds, which yield one token or two, a
        superset by the slots
        whose unread rounds accepted a draft past their end, which the
        device has frozen already (their rows come back with count 0)."""
        unread = self._unread[2] if self._unread is not None else {}
        pending = {req.slot for req, _, _ in self._pending}
        return {s: r for s, r in enumerate(self._slot_req)
                if r is not None and s not in self._prefilling
                and r.max_new_tokens - len(r.tokens)
                > (unread.get(s) is r) + (s in pending)}

    def _dispatch(self, live: dict):
        """ONE decode dispatch for the live set, from the loop state on
        the device: nothing is sent. Returns the block as dispatched,
        ``(tokens, routing, live)`` with device arrays — tokens [S] of a
        plain step, [1, S, 4] of a round. The host's cursors move on
        as the program moves the device's (a speculative round's count is
        the device's to say: booked when its block is read, ``_emit``)."""
        if self.engine.spec:
            return self.engine.decode_spec() + (live,)
        self._cursors[list(live)] += 1
        return self.engine.decode() + (live,)

    def _vacate(self) -> None:
        """Free the slots whose requests the block just dispatched is
        CERTAIN to end — it holds a token for a slot at least, and these
        are owed no more than that — so the next
        step's admission takes them, as it did when the block was read in
        the step that dispatched it. The request is off the slot table
        (no sweep reaches it: the device has finished it) and stays in the
        unread block, which books its last tokens and retires it when it
        is read; ``left`` keeps what ``_emit`` needs of the slot and a new
        tenant overwrites, the instant of its last token."""
        if self._unread is None:
            return
        _, _, live, left = self._unread
        for slot, req in live.items():
            # every block before this one is booked: ``tokens`` is current
            if (req.max_new_tokens - len(req.tokens) <= 1
                    and self._slot_req[slot] is req):
                self._slot_req[slot] = None
                left[slot] = self._last_tok_s[slot]

    def _book_kv_blocks(self, live: dict) -> dict:
        """Count what the dispatch for ``live`` reads of the pool, in the
        kernel's key blocks a layer at the cursors it starts from
        (``key_block_span``, the function the kernel's work list comes
        from): ``kv_blocks`` of the live slots, ``kv_blocks_pool`` of
        every slot, frozen cursors included — into the server's totals
        and two registry counters, and returned as the ``serve.decode``
        span's attrs (none where the pool has no kernel read, or nothing
        is dispatched); beside them ``kv_rows`` (a pool of K/V rows: the
        rows the live slots hold up to their cursors, over the 'attn'
        layers; absent for a model none of whose layers keeps rows a
        position) and ``state_slots`` (a model with 'kda', 'gdn' or 'ret'
        layers: the live slots, whose recurrent state the step moves). For a model with
        learned sparse attention the attrs are ``keys_cached``,
        ``keys_attended`` and ``rows_gathered`` instead: the latent rows the live slots hold up to their cursors,
        the rows their queries attend, and the rows the step's gathers fetch
        (``index_topk`` a trip of the program's own work list,
        ``dsa.live_slots``), over the 'mla' layers. No device read."""
        attrs = {}
        if live and self.model.dsa:
            # a query at cursor c has c + 1 rows behind it (its own among
            # them) in every 'mla' layer and attends min(c + 1, topk)
            layers = len(self.model.layers_of("mla"))
            cached = self._cursors[list(live)] + 1
            topk = min(self.model.dsa["topk"], self.max_len)
            owing = np.isin(np.arange(self.slots), list(live))
            pool = self.model.dsa.get("pool", 1)
            attended = np.minimum(cached, topk)
            # rows a trip of the program's work list gathers
            width = selection_width(self.model.dsa, self.max_len)
            if pool > 1:
                # a query at cursor c scores the c // pool pools that end
                # before its own, attends the topk / pool best whole and its
                # own open pool up to itself; the gather fetches that many
                # rows and the open pool's others
                scored = (cached - 1) // pool
                chosen = np.minimum(scored, topk // pool)
                tail = (cached - 1) % pool + 1
                attended = chosen * pool + tail
                attrs = {"pools_scored": int(scored.sum()) * layers,
                         "pools_selected": int(chosen.sum()) * layers,
                         "tail_attended": int(tail.sum()) * layers}
                self.pools_scored += attrs["pools_scored"]
                self.pools_selected += attrs["pools_selected"]
                self.tail_attended += attrs["tail_attended"]
            attrs.update(keys_cached=int(cached.sum()) * layers,
                         keys_attended=int(attended.sum()) * layers,
                         rows_gathered=int(live_slots(owing, self.slots)[1])
                         * width * layers)
            self.keys_cached += attrs["keys_cached"]
            self.keys_attended += attrs["keys_attended"]
            self.rows_gathered += attrs["rows_gathered"]
        if live and self._kv_reads:
            # a query at cursor c attends c + 1 rows (its own among them)
            # of every 'attn' layer, its window's at most
            held = self._cursors[list(live)] + 1
            rows = {True: 0, False: 0}      # {the layers have a window: rows}
            for layers, window, t_max, *_ in self._kv_reads:
                rows[window is not None] += int(np.minimum(
                    held, window or t_max).sum()) * layers
            attrs["kv_rows"] = rows[True] + rows[False]
            self.kv_rows += attrs["kv_rows"]
            if self.model.by_layer:
                attrs.update(kv_rows_window=rows[True],
                             kv_rows_full=rows[False])
                self.kv_rows_window += rows[True]
                self.kv_rows_full += rows[False]
        if live and self._recurrent:
            # a model none of whose layers keeps rows a position says so
            attrs.setdefault("kv_rows", 0)
            attrs["state_slots"] = len(live)
            self.state_slots += len(live)
        if live and self._kv_reads and all(
                r[-1] is not None for r in self._kv_reads):
            # one group: the blocks a layer, as every layer reads the same;
            # a model that gives a window a layer: over all its 'attn'
            # layers, each group's blocks times its layers
            every = len(self._kv_reads) > 1
            attrs.update(kv_blocks=0, kv_blocks_pool=0)
            for layers, window, t_max, hkv, ring, block in self._kv_reads:
                lo, hi = key_block_span(
                    self._cursors, self._cursors, block=block, hkv=hkv,
                    window=None if ring else window, t_max=t_max)
                blocks = (hi - lo + 1) * (layers if every else 1)
                attrs["kv_blocks"] += int(blocks[list(live)].sum())
                attrs["kv_blocks_pool"] += int(blocks.sum())
            self.kv_blocks += attrs["kv_blocks"]
            self.kv_blocks_pool += attrs["kv_blocks_pool"]
            self._reg.counter("serve_decode_kv_blocks_total").inc(
                attrs["kv_blocks"])
            self._reg.counter("serve_decode_kv_blocks_pool_total").inc(
                attrs["kv_blocks_pool"])
        return attrs

    def _read_block(self, toks, routing, live_rows: int, decode=False):
        """One program's tokens on the host — the loop's one sanctioned
        readback — with the routing that came with them: ``(tokens,
        rows)``. A model with routed experts hands its ``[layers,
        experts]`` load over in the same read-back; it is booked here:
        ``moe_expert_load`` (``stats()``), the counter
        ``serve_moe_routed_pairs_total``, the gauge
        ``serve_moe_max_expert_share`` (the busiest expert's share of its
        layer's pairs in this dispatch) and, on the span open now,
        ``serve.decode`` or ``serve.first_token``, ``experts_touched`` (the
        (layer, expert) cells that received a token) and ``experts_read``
        (the cells whose matrices the program fetched: the same where it
        was traced in the reached form, layers x held where not;
        ``models/routed_experts.py``). Where a layer took the sorted form,
        the sorted rows its passes ran and the pairs held here they ran
        them for are booked too (``stats()``: ``moe_rows_run``,
        ``moe_pairs_run``) and open a span of their own, ``serve.passes``,
        with both as attrs: a profiler trace keeps only the attrs a span
        is opened with (``monitor/trace.py``). The rows' experts and
        weights come in the same array (``engine._stack_routing``):
        ``rows`` = ``(experts, weights)``, None for a dense model.
        ``live_rows`` is how many rows of the program held a token (a
        prompt's length, a decode block's live slots, twice that for a round
        that verifies two candidates a slot): ``moe_rows``.

        A model with learned sparse attention hands over ``(routing,
        selection)`` (``engine._record``); the third value returned is the
        selection on the host where ``record_routing`` keeps it (a quarter
        of a megabyte a decode step at 16 slots, 2 layers, 2,048 keys:
        otherwise it stays on the device), else None."""
        import jax

        selection = None
        if isinstance(routing, tuple):
            routing, selection = routing
            if not self.record_routing:
                selection = None
        if routing is None:
            toks, selection = jax.device_get((toks, selection))
            return np.asarray(toks), None, selection
        toks, packed, selection = jax.device_get((toks, routing, selection))
        # a speculative round hands over its array as [1, L, ...]
        rows_run = pairs_run = 0
        for one in (packed if packed.ndim == 3 else packed[None]):
            load, *rows, read, run = unpack_routing(
                one, self.model.experts_held, self.model.experts_per_token)
            touched, read = int(np.count_nonzero(load)), int(read.sum())
            rows_run += int(run.sum())
            pairs_run += int(load[run > 0].sum())
            self.moe_expert_load += load
            self.moe_rows += live_rows
            if decode:
                self.moe_decode_blocks += 1
                self.moe_decode_touched += touched
                self.moe_decode_read += read
            pairs = int(load.sum())
            if pairs:
                self._reg.counter("serve_moe_routed_pairs_total").inc(pairs)
                self._reg.gauge("serve_moe_max_expert_share").set(
                    float((load.max(axis=1) / np.maximum(
                        load.sum(axis=1), 1)).max()))
        span = tracer().current()
        if span is not None:
            span.attrs["experts_touched"] = touched
            span.attrs["experts_read"] = read
        if rows_run:
            self.moe_rows_run += rows_run
            self.moe_pairs_run += pairs_run
            with tracer().span("serve.passes", moe_rows_run=rows_run,
                               moe_pairs_run=pairs_run):
                pass
        return toks, rows, selection

    def _sweep_expired(self) -> None:
        """The retirement loop's deadline check: an in-flight request
        past its deadline frees its slot NOW (shed, ``in_flight``), and
        a canceled hedge loser retires quietly — both before admission,
        so the freed slots take new work this very boundary. The slots
        stop decoding on the device (``release_slot``), and a token of
        theirs in the unread block is dropped when it is read."""
        now = self.clock()
        for slot in self._live_slots():
            req = self._slot_req[slot]
            if req.canceled:
                req.state = "canceled"
                self._reg.counter("serve_requests_total").inc(
                    event="canceled")
            elif req.expired(now):
                self._shed(req, where="in_flight", reason="deadline",
                           now=now)
            else:
                continue
            self._slot_req[slot] = None
            if slot in self._prefilling:    # its carry goes back
                self._prefilling.pop(slot)[0].close()
            self.engine.release_slot(slot)
            self._cursors[slot] = 0

    def step(self) -> bool:
        """One scheduler iteration: shed expired/canceled slots; admit —
        dispatch only: a request's one program (its prefill and its slot's
        loop state) is queued and its first token left pending on the
        device; one decode dispatch (a plain step, or a round of the
        model's own module) for every slot that may owe a token, the ones
        just admitted among them; then read and book a token block — the
        one dispatched a step EARLIER — and the pending first tokens, in
        this same step: the chip runs this step's dispatch meanwhile, and
        at no admission does it wait for the host. The slots this
        step's dispatch is certain to finish are free for the next step's
        admission (``_vacate``). Returns False when
        nothing was dispatched or read and no prompt is part-way through
        its prefill blocks (the caller may idle)."""
        with tracer().span("serve.step") as sp:
            self._sweep_expired()
            sp.attrs["admitted"] = self._admit()
            live = self._owed()
            self._reg.gauge("serve_queue_depth").set(len(self.queue))
            self._reg.gauge("serve_slot_occupancy").set(self.occupancy())
            if not live and self._unread is None and not self._pending:
                return bool(self._prefilling)   # a prefill block ran
            sp.attrs["live"] = len(live)
            self._decode(live)
            self._vacate()
            return True

    def _decode(self, live: dict) -> None:
        """The ``serve.decode``, ``serve.first_token`` and ``serve.emit``
        phases: dispatch a block for ``live`` (none when empty), then read
        the block that was unread, the one dispatched a step earlier — the
        same order for a plain step and a round — then the first tokens of
        this step's admissions (``_read_first_tokens``), whose prefills
        ran before the block just dispatched, and book the block read."""
        unread = self._unread
        ahead = bool(live) and unread is not None
        with tracer().span("serve.decode", live=len(live),
                           kind=self._decode_kind, ahead=int(ahead),
                           **self._book_kv_blocks(live)) as sp:
            # dispatched while ``_unread`` is still the block before it;
            # ``_vacate`` fills the block's last part
            self._unread = self._dispatch(live) + ({},) if live else None
            if ahead:
                self.decode_ahead += 1
                self._reg.counter("serve_decode_ahead_total").inc()
            if unread is not None:
                block = self._read_unread(unread, sp)
        if self._pending:
            self._read_first_tokens(bool(live))
        if unread is None:      # the first dispatch after idling
            return
        with tracer().span("serve.emit") as emit:
            emit.attrs["tokens"], emit.attrs["retired"] = self._emit(
                *unread[2:], *block)

    def _read_unread(self, unread, sp) -> tuple:
        """The block ``unread`` on the host, inside the ``serve.decode``
        span ``sp``, as ``_emit`` takes it after its slots: ``(tokens,
        counts, rows, selection, drafts)``; a round's counts are booked
        here, on the span."""
        candidates = 1 + bool(self.model.mtp)   # rows a slot and layer
        toks, rows, selection = self._read_block(
            *unread[:2], candidates * len(unread[2]), decode=True)
        counts = drafts = None
        if self.engine.spec:
            # the block's one round, [S, 4]: the tokens it yields, the
            # two it may yield, the draft it verified
            counts, toks, drafts = (toks[0, :, 0], toks[0, :, 1:3],
                                    toks[0, :, 3])
            # what the device says of the round (no further read)
            c = counts[list(unread[2])]
            rounds = int(np.count_nonzero(c > 0))
            sp.attrs.update(
                rounds=rounds, proposed=rounds * self.engine.spec_tokens,
                accepted=int(np.maximum(c - 1, 0).sum()),
                emitted=int(c.sum()))
            self.spec_rounds += rounds
            self.spec_emitted += sp.attrs["emitted"]
            # every slot of the live set had finished on a draft that
            # an unread round accepted: a dispatch for nothing
            self.empty_dispatches += rounds == 0
        return toks, counts, rows, selection, drafts

    def flush(self) -> None:
        """Read and book the block the host has not read yet (no-op with
        none): afterwards every dispatched token is on its request and
        the host's slot table agrees with the device's loop state."""
        if self._unread is not None:
            self._decode({})

    def _emit(self, live: dict, left: dict, toks, counts, rows,
              selection=None, drafts=None) -> Tuple[int, int]:
        """Book one dispatch's token block: per slot that was live in it
        the tokens it takes (``toks`` [S] of a plain step; [S, 2] of a
        round, of which ``counts`` [S] says how many a slot yields), TPOT
        observations, retirement; ``rows`` is
        the same block's routing, ``selection`` its key selections and
        ``drafts`` [S] the drafts its round verified. A
        slot whose request was swept while the block was unread takes
        nothing; one whose request left it at the dispatch (``left``,
        ``_vacate``) books it all the same, and touches nothing of the
        slot, which may hold its next tenant. Returns ``(tokens emitted,
        requests retired)``."""
        now = self.clock()
        self.steps += 1
        self.slot_dispatches += len(live)
        self._reg.counter("serve_decode_steps_total").inc()
        tpot = self._reg.histogram("serve_tpot_seconds",
                                   buckets=_LATENCY_BUCKETS)
        emitted_total = retired = 0
        proposed0, accepted0 = self.spec_proposed, self.spec_accepted
        for slot, req in live.items():
            tenant = self._slot_req[slot]
            if tenant is not req and slot not in left:
                continue
            got: List[int] = []
            if counts is None:      # owed a token when it was dispatched
                got.append(int(toks[slot]))
                if req.routing is not None:
                    # the row that emitted this token
                    req.routing.append(tuple(a[:, slot:slot + 1]
                                             for a in rows))
                    if selection is not None:
                        req.selection.append(selection[:, slot:slot + 1])
            elif counts[slot] > 0:
                c = int(counts[slot])
                take = min(c, req.max_new_tokens - len(req.tokens))
                if req.drafts is not None:
                    # the round verified a draft for the position after
                    # its cursor's (the tokens so far end on the cursor),
                    # and made ``take`` positions permanent: their rows
                    # of the S x 2 candidates', every layer's
                    req.drafts.append((
                        req.prompt.shape[0] + len(req.tokens),
                        int(drafts[slot])))
                    req.routing.append(tuple(
                        a.reshape(a.shape[0], self.slots, 2, -1)[
                            :, slot, :take] for a in rows))
                # the slot's cursor is this request's to move while
                # it holds the slot, or left it and no tenant has come
                if tenant is req or tenant is None:
                    self._cursors[slot] += c
                got.extend(int(t) for t in toks[slot, :take])
                self.spec_proposed += self.engine.spec_tokens
                self.spec_accepted += c - 1
            req.tokens.extend(got)
            emitted_total += len(got)
            # a round's two tokens land together: spread the
            # dispatch interval evenly so TPOT keeps one observation
            # per token and sums to the true wall span
            last = left[slot] if slot in left else self._last_tok_s[slot]
            interval = (now - last) / max(1, len(got))
            for _ in got:
                tpot.observe(interval)
            if tenant is req:
                self._last_tok_s[slot] = now
            if len(req.tokens) >= req.max_new_tokens:
                self._retire(slot, req, now)
                retired += 1
        self.decode_tokens += emitted_total
        self._reg.counter("serve_tokens_total").inc(emitted_total)
        if self.engine.spec:
            if self.spec_proposed > proposed0:
                self._reg.counter("serve_spec_proposed_total").inc(
                    self.spec_proposed - proposed0)
            if self.spec_accepted > accepted0:
                self._reg.counter("serve_spec_accepted_total").inc(
                    self.spec_accepted - accepted0)
        # re-publish after retirement: a drained server must read 0,
        # not the pre-retirement batch width
        self._reg.gauge("serve_slot_occupancy").set(self.occupancy())
        return emitted_total, retired

    def drain(self, max_steps: Optional[int] = None) -> int:
        """Step until queue and slots are empty; returns steps taken."""
        taken = 0
        while self.busy():
            self.step()
            taken += 1
            if max_steps is not None and taken >= max_steps:
                break
        self.flush()
        return taken

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Artifact-ready snapshot: compile counts, pool footprint,
        request/dispatch totals and ratios (dispatches/token,
        tokens/dispatch). Reads the unread
        block first (``flush``), so the counts are of every dispatch."""
        self.flush()
        out = {
            "slots": self.slots,
            "max_len": self.max_len,
            "queue_depth": len(self.queue),
            "occupancy": self.occupancy(),
            "steps": self.steps,
            "finished": len(self.finished),
            "shed": len(self.shed),
            "shed_by_class": dict(self.shed_by_class),
            "expired_in_queue": self.expired_in_queue,
            "expired_in_flight": self.expired_in_flight,
            "kv_dtype": self.engine.kv_dtype,
            "kv_pool_bytes": self.engine.cache.nbytes,
            # the pool's bytes by what they are: K/V rows, latent
            # rows, an indexer's keys, recurrent matrices, convolution tails,
            # normalisers
            "state_bytes": self.engine.cache.nbytes_by_kind,
            # slots a decode dispatch served, mean (with rounds: the slots
            # that MAY owe a token, ``_owed``'s superset)
            "live_slots_per_step": (
                round(self.slot_dispatches / self.steps, 4)
                if self.steps else None),
            # what one concurrent request costs in pool HBM
            # (kv_per_slot_bytes * slots == kv_pool_bytes)
            "kv_per_slot_bytes": self.engine.cache.per_slot_nbytes,
            # TP serving: the pool shards its head axis over ``model``,
            # so the per-chip footprint is kv_pool_bytes / kv_shards
            "kv_shards": self.engine.cache.n_shard,
            "decode_dispatches": self.steps,
            # dispatches issued while the previous block was unread, of
            # all dispatches: the share of decode steps the chip did not
            # wait for the host (0 on the first step after the server was
            # empty), on either kind of block
            "decode_ahead_share": (round(self.decode_ahead / self.steps, 4)
                                   if self.steps else None),
            # admissions whose first token the host read with a decode block
            # already dispatched behind their prefill: the chip did not
            # wait for the host at them (every admission but one with
            # ``max_new_tokens`` 1 into a server no slot of which is live)
            "admit_ahead": self.admit_ahead,
            # dispatches of rounds in which every slot of the live set had
            # ended on a draft accepted in the block before (count 0 in
            # every row): the cost of dispatching on "may owe a token"
            "empty_dispatches": self.empty_dispatches,
            # key blocks a layer the pool kernel fetched for the slots
            # dispatched, of what reading every slot's cursor (live or
            # frozen) would have fetched — None where the pool has no
            # kernel read
            "kv_blocks": self.kv_blocks,
            "kv_blocks_pool": self.kv_blocks_pool,
            "kv_blocks_share": (round(self.kv_blocks / self.kv_blocks_pool, 4)
                                if self.kv_blocks_pool else None),
            # K/V rows the dispatched slots held up to their cursors, over
            # the 'attn' layers, and slots whose recurrent state a step
            # moved, summed over the decode dispatches
            "kv_rows": self.kv_rows,
            **({"kv_rows_window": self.kv_rows_window,
                "kv_rows_full": self.kv_rows_full}
               if self.model.by_layer else {}),
            "state_slots": self.state_slots,
            "decode_tokens": self.decode_tokens,
            "dispatches_per_token": (
                round(self.steps / self.decode_tokens, 4)
                if self.decode_tokens else None),
            # tokens one dispatch yields across the whole batch ...
            "accepted_tokens_per_dispatch": (
                round(self.decode_tokens / self.steps, 4)
                if self.steps else None),
            # ... vs per live slot: exactly 1.0 of plain steps, more
            # ONLY through accepted drafts (up to 2 with a module)
            "tokens_per_slot_dispatch": (
                round(self.decode_tokens / self.slot_dispatches, 4)
                if self.slot_dispatches else None),
            "speculative": self.engine.spec,
            "compiles": self.engine.compile_counts(),
        }
        if self.model.dsa:
            out["keys_cached"] = self.keys_cached
            out["keys_attended"] = self.keys_attended
            out["rows_gathered"] = self.rows_gathered
            out["keys_attended_share"] = (
                round(self.keys_attended / self.keys_cached, 4)
                if self.keys_cached else None)
            out["prefill_blocks"] = self.prefill_blocks
            if self.model.dsa.get("pool", 1) > 1:
                out["pools_scored"] = self.pools_scored
                out["pools_selected"] = self.pools_selected
                out["tail_attended"] = self.tail_attended
                out["recurrence_blocks"] = self.recurrence_blocks
        if self.model.num_experts:
            out["moe_expert_load"] = self.moe_expert_load.tolist()
            out["moe_rows"] = self.moe_rows
            out["moe_rows_run"] = self.moe_rows_run
            out["moe_pairs_run"] = self.moe_pairs_run
            # moe_rows: the live rows the expert layers saw (prompt tokens
            # and decode slots); (layer, held expert) cells a decode block
            # reached, and cells whose matrices its program fetched, means;
            # and the (token, expert) pairs that landed on an expert held
            # here, per live row and layer (k when every expert is here;
            # k x held / num_experts in expectation for a share);
            # moe_rows_run over moe_pairs_run is what the sorted form's
            # passes ran for each pair they served (1 at best)
            blocks = self.moe_decode_blocks
            out["moe_experts_touched_per_step"] = (
                round(self.moe_decode_touched / blocks, 4) if blocks
                else None)
            out["moe_experts_read_per_step"] = (
                round(self.moe_decode_read / blocks, 4) if blocks else None)
            layer_rows = self.moe_rows * len(self.moe_expert_load)
            out["moe_pairs_here_per_token"] = (
                round(float(self.moe_expert_load.sum()) / layer_rows, 4)
                if layer_rows else None)
        if self.engine.spec:
            out["spec_tokens"] = self.engine.spec_tokens
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_accept_rate"] = (
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else None)
            out["spec_rounds"] = self.spec_rounds
            out["spec_emitted"] = self.spec_emitted
        return out
