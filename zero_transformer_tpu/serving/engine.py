"""Continuous-batching scheduler + request lifecycle.

``ServingEngine`` turns the repo's single-request jitted decode path
(``inference/generate.py``) into a concurrent serving surface:

- requests queue behind a bounded admission queue (backpressure: a full
  queue REJECTS at submit time rather than stacking unbounded latency);
- K/V lives in ONE place: the block-table page pool of
  ``slots.PagedKVCache``. KV HBM is ``page_pool_tokens`` positions whatever
  the slot count, admission reserves each request's worst case so capacity
  pressure queues instead of faulting, and a prefix-cache hit maps shared
  pages by refcount instead of copying bytes;
- free slots admit queued requests, and the prompt prefills CHUNKED:
  ``prefill_chunk`` tokens per tick, written through the slot's block table
  into the pool by one ``[rows, chunk]`` program
  (``_paged_chunk_prefill_impl``) that advances EVERY mid-prefill slot at
  once — so a long prompt never stalls active streams for its full prefill
  (the Sarathi-Serve interleaving), multiple queued prompts prefill as one
  batch (admission is inherently batched), and there is no per-prompt-length
  compile. The program computes ``PREFILL_ROWS`` rows, not ``n_slots``:
  the slots that prefill this tick are handed to it that many at a time
  (one dispatch for nearly every tick of a server under steady load, one
  more for each further ``PREFILL_ROWS`` slots of a burst), so a prompt
  arriving alone does not pay for every slot's row. A chunk-aligned
  token-prefix index over page ids (``serving/prefix_cache.py``) lets
  repeated system prompts skip straight to the first novel chunk;
- every ``step()`` runs ONE fused decode step across all slots
  (``_fused_step_impl``) — padded and masked so the compiled program is
  identical whatever the occupancy — then retires slots that hit EOS, their
  token budget, a deadline, or a cancellation. With ``draft_k > 0`` the step
  is the SPECULATIVE twin (``_spec_step_impl``): ``draft_k`` host-proposed
  prompt-lookup drafts per slot verified in the same single forward,
  committing ``1 + n_acc`` tokens per tick (greedy ≡ plain decode
  bit-for-bit; sampling via the standard rejection rule);
- each request carries its OWN rng chain and repetition-penalty mask,
  threaded per-slot through the fused step, so its token trajectory is
  IDENTICAL to what single-request ``generate()`` produces with the same
  seed (tested byte-for-byte).

Everything device-side is shape-static: admissions and retirements never
recompile anything. The engine itself is synchronous (``step()``); a serving
front end drives it from a background thread (``run()``) and talks to it
through thread-safe ``submit()`` / ``RequestHandle``.

Resilience (``serving/resilience.py`` owns the primitives):

- the engine carries an explicit ``Lifecycle`` (STARTING -> READY ->
  DEGRADED -> DRAINING -> STOPPED) that ``/healthz`` reflects;
- the decode tick is SUPERVISED: an exception inside one tick fails only
  the slots it poisons (retryable error to those clients), and a circuit
  breaker trips the engine into DEGRADED and rebuilds the jitted step
  after ``breaker_threshold`` consecutive faults (bounded by
  ``max_rebuilds``, then the fault escalates out of ``run()``);
- a per-tick non-finite-logits guard (the training anomaly guard's
  predicate, ``resilience.anomaly.nonfinite_rows``) retires only affected
  slots;
- ``begin_drain`` stops admission (queued requests finish as retryable
  rejections), lets in-flight generations complete up to a deadline, then
  force-finishes — SIGTERM maps here;
- ``reload_params`` validates a standby tree off the tick thread and swaps
  it between ticks without dropping a slot;
- admission sheds requests whose deadline is provably infeasible given
  queue depth and the measured ITL EWMA (fast honest 503s, not timeout
  storms).

Observability (``obs/`` owns the primitives — docs/OBSERVABILITY.md):

- every request carries a REQUEST ID (client-supplied ``X-Request-Id`` or
  generated at admission) and emits a well-nested span tree —
  ``request`` ⊃ {``queue``, ``prefill``, ``decode``} — into the engine's
  ring-buffered ``Tracer`` when it reaches a terminal state, whatever that
  state is (done/shed/expired/cancelled/faulted). Per-tick phase spans
  (``prefill_chunk``, ``decode_step``, ``emit``) land on the ``engine``
  track, so a Perfetto view shows where each tick's milliseconds went;
- latency metrics live in fixed-bucket ``obs.Histogram``s
  (``serve_ttft_seconds`` etc.): ``metrics_snapshot()`` percentiles are
  O(buckets) bucket walks and ``prometheus_text()`` renders the text
  exposition — neither touches the scheduler lock (pre-PR7 every scrape
  sorted three 10k-sample deques under it);
- a ``FlightRecorder`` keeps the last N tick summaries/events in RAM and
  dumps them (spans included) on breaker-open, drain, and abort;
- ``request_profile(n)`` stages a ``jax.profiler`` capture of the next n
  ticks (``POST /admin/profile``), started/stopped by the tick thread only.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import queue as queue_mod
import re
import threading
import time
import uuid
from collections import deque
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from zero_transformer_tpu.analysis.runtime import (
    CompileFamilyExceeded,
    bounded_dispatch,
)
from zero_transformer_tpu.analysis.memory import kv_bytes_per_token
from zero_transformer_tpu.config import resolve_dtype
from zero_transformer_tpu.obs import (
    LATENCY_BUCKETS,
    FlightRecorder,
    ProfileWindow,
    Registry,
    Tracer,
    hbm_device_stats,
)

from zero_transformer_tpu.inference.generate import (
    _in_mesh,
    decode_model,
    serving_params,
)
from zero_transformer_tpu.inference.sampling import (
    NEG_INF,
    SamplingConfig,
    process_logits,
    sample_token,
)
from zero_transformer_tpu.inference.speculative import ngram_propose
from zero_transformer_tpu.resilience.detect import nonfinite_rows
from zero_transformer_tpu.serving.prefix_cache import PagedPrefixIndex
from zero_transformer_tpu.serving.qos import (
    BROWNOUT_RUNGS,
    ClassQueue,
    QosPolicy,
    TenantBuckets,
    reserved_above,
    rung_at_least,
)
from zero_transformer_tpu.serving.resilience import (
    DEGRADED,
    DRAINING,
    READY,
    STOPPED,
    CircuitBreaker,
    ItlEwma,
    Lifecycle,
    ReloadError,
    infeasible_deadline,
    validate_reload,
)
from zero_transformer_tpu.serving.slots import (
    INDEX_LEAVES,
    STATE_LEAVES,
    TABLE_LEAF,
    PagedKVCache,
    _leaf_name,
)

# characters stripped from client-supplied request ids (keep the usual
# trace-id alphabets: alnum plus - _ . : / =)
_RID_UNSAFE = re.compile(r"[^A-Za-z0-9._:/=-]")

# request terminal states
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
CANCELLED = "cancelled"
EXPIRED = "expired"
REJECTED = "rejected"
FAILED = "failed"  # the ENGINE died, not the request
# the stream now lives on another replica (its pages shipped there); the
# handle's ``migrated_to`` names the new home — a router attaches there and
# the client's stream continues with ZERO recomputed tokens
MIGRATED = "migrated"

_FINISHED = (DONE, CANCELLED, EXPIRED, REJECTED, FAILED, MIGRATED)

# engine roles (disaggregated prefill/decode fleets): a PREFILL replica runs
# only chunked prefill at max batch and ships every finished stream's pages
# to the decode replica the request names (``prefill_to``); a DECODE replica
# serves imported streams (and plain requests, as the recompute fallback);
# MIXED is the classic single-replica behavior.
ROLES = ("mixed", "prefill", "decode")


@dataclasses.dataclass
class Request:
    """One generation request, in token-id space (detokenization is the
    front end's job — the engine is tokenizer-agnostic)."""

    prompt: Sequence[int]
    max_new_tokens: int
    seed: int = 0
    # absolute deadline on the engine's clock (``engine.now()``); None = no
    # deadline. Enforced both in the queue and mid-decode.
    deadline: Optional[float] = None
    # disaggregation: when set, the finished prefill's pages ship to this
    # replica URL instead of decoding here (required on prefill-role
    # engines; honored on mixed engines too)
    prefill_to: Optional[str] = None
    # overload isolation (PR 18): the billing identity and QoS class this
    # request admits under. Unknown classes normalize to the policy's
    # default at submit; "anon"/default is the full pre-QoS behavior.
    tenant: str = "anon"
    qos: str = "standard"


class RequestHandle:
    """Thread-safe view of a submitted request: token stream + final state."""

    def __init__(self, request: Request, rid: int, submitted_at: float,
                 request_id: Optional[str] = None):
        self.request = request
        self.id = rid
        # correlation id: client-supplied (X-Request-Id) or generated —
        # returned in the response header and the SSE done event, and the
        # TRACK key of this request's span tree. SANITIZED to a safe header
        # charset: the value is echoed verbatim into a response header, so
        # CR/LF would let a client inject arbitrary headers (response
        # splitting) and non-latin-1 would crash send_header mid-response;
        # a client id that sanitizes to nothing falls back to a generated one
        if request_id:
            clean = _RID_UNSAFE.sub("", str(request_id))[:128]
            self.rid = clean or uuid.uuid4().hex
        else:
            self.rid = uuid.uuid4().hex
        self.submitted_at = submitted_at
        self.status = QUEUED
        self.tokens: List[int] = []
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.error: Optional[str] = None
        # retryable=True marks a failure/rejection the CLIENT should retry
        # (tick fault, drain, shed, breaker) — the server maps it to 503 +
        # Retry-After; invalid requests stay non-retryable 400s
        self.retryable = False
        self.retry_after: Optional[float] = None
        # terminal status ``migrated``: the replica URL now serving this
        # stream (the router attaches there and continues the client's SSE
        # with zero token replay)
        self.migrated_to: Optional[str] = None
        # how many prompt tokens a prefix-cache hit covered at admission
        # (0 = cold/miss/disabled) — the loadgen splits TTFT by this
        self.prefix_hit_tokens = 0
        # when the request left the queue for a slot: first_token_at minus
        # this is the prefill+first-decode latency the ENGINE controls
        # (TTFT minus queue wait), the clean denominator for prefix-cache
        # attribution under load
        self.admitted_at: Optional[float] = None
        # when the prompt's K/V finished landing in the slot (install into
        # the decode set) — the prefill/decode span boundary
        self.prefill_done_at: Optional[float] = None
        # the engine's Tracer; the lifecycle span tree is emitted from the
        # timestamps above in ONE batch at _finish (zero per-token cost)
        self._tracer: Optional[Tracer] = None
        # per-request cost ledger (obs/fleet.py PR 15): plain-int counters
        # the tick thread bumps — prefill chunks, decode ticks, drafted/
        # accepted tokens, pages held x ticks. Rides the page-span payload
        # on migration so the counts stay CUMULATIVE across replicas; the
        # ms split is computed from the lifecycle timestamps at read time,
        # with _ledger_ms_base carrying the milliseconds already spent on
        # earlier hops of a migrated stream.
        self.ledger: Dict[str, int] = {
            "prefill_chunks": 0, "decode_ticks": 0, "tokens_out": 0,
            "draft_tokens": 0, "accepted_tokens": 0, "pages_held_ticks": 0,
            "migrations": 0,
        }
        self._ledger_ms_base = {"queue_ms": 0.0, "prefill_ms": 0.0,
                                "decode_ms": 0.0}
        # propagated trace context: the router's hop index for this
        # dispatch (span attrs carry it so the stitched fleet trace can
        # assert hop ordering across processes)
        self.trace_hop: Optional[int] = None
        self._events: queue_mod.Queue = queue_mod.Queue()
        self._done = threading.Event()
        self._cancel = threading.Event()
        # bounded emit buffer (slow-client protection): once a STREAMING
        # consumer has attached (the server's SSE pump sets
        # consumer_attached) and stops draining, token events past
        # emit_buffer_max are dropped and ``overflowed`` trips — the
        # scheduler then finishes the stream retryably instead of holding
        # its slot/pages for a reader that went away. Non-streaming
        # waiters (result()) never attach, so their buffering stays
        # bounded by max_new_tokens exactly as before. The terminal
        # ("done", status) event is NEVER dropped.
        self.emit_buffer_max: int = 1024
        self.consumer_attached = False
        self.overflowed = False

    # -- consumer side -----------------------------------------------------

    def cancel(self) -> None:
        """Ask the scheduler to drop this request (queued or mid-decode).
        Takes effect at the next tick boundary; the handle finishes with
        status ``cancelled``."""
        self._cancel.set()

    def next_event(self, timeout: Optional[float] = None):
        """Blocking pop of the next ``("token", id)`` / ``("done", status)``
        event, or None when ``timeout`` elapses first (lets a server poll
        client liveness between events without killing the stream)."""
        try:
            return self._events.get(timeout=timeout)
        except queue_mod.Empty:
            return None

    def stream(self, timeout: Optional[float] = None):
        """Yield token ids as they generate; returns when the request
        reaches a terminal state. ``timeout`` bounds the wait per token
        (TimeoutError, same contract as ``result``)."""
        while True:
            event = self.next_event(timeout=timeout)
            if event is None:
                raise TimeoutError(
                    f"request {self.id} produced no token in {timeout}s"
                )
            kind, value = event
            if kind == "token":
                yield value
            else:
                return

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until terminal, then return all emitted token ids
        (including the EOS token when one was sampled)."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(f"request {self.id} still {self.status}")
        return list(self.tokens)

    def ledger_snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        """The request's cost ledger as the terminal event reports it:
        cumulative counters plus the queue/prefill/decode millisecond
        split from the lifecycle timestamps (hop-local wall added to the
        base a migrated stream carried in). ``now`` lets a LIVE snapshot
        (the migration export) account wall time up to this instant —
        without it a mid-decode hop would ship decode_ms=0 and the
        cumulative split would silently lose the source hop's time."""
        sub = self.submitted_at
        adm = self.admitted_at
        pre = self.prefill_done_at
        fin = self.finished_at
        if fin is not None:
            end = fin
        elif now is not None:
            end = now
        else:
            end = pre or adm or sub
        queue_ms = ((adm if adm is not None else end) - sub) * 1e3
        prefill_ms = (
            ((pre if pre is not None else end) - adm) * 1e3
            if adm is not None else 0.0
        )
        decode_ms = (end - pre) * 1e3 if pre is not None else 0.0
        base = self._ledger_ms_base
        return {
            **{k: int(v) for k, v in self.ledger.items()},
            "queue_ms": round(base["queue_ms"] + max(0.0, queue_ms), 3),
            "prefill_ms": round(base["prefill_ms"] + max(0.0, prefill_ms), 3),
            "decode_ms": round(base["decode_ms"] + max(0.0, decode_ms), 3),
        }

    # -- scheduler side ----------------------------------------------------

    def _emit(self, token: int, now: float) -> None:
        if self.first_token_at is None:
            self.first_token_at = now
        self.tokens.append(token)
        if (
            self.consumer_attached
            and self._events.qsize() >= self.emit_buffer_max
        ):
            # stalled streaming reader: stop buffering (the scheduler
            # notices ``overflowed`` this tick and finishes retryably)
            self.overflowed = True
            return
        self._events.put(("token", token))

    def _finish(
        self,
        status: str,
        now: float,
        error: Optional[str] = None,
        retryable: bool = False,
        retry_after: Optional[float] = None,
    ) -> None:
        self.status = status
        self.error = error
        self.retryable = retryable
        self.retry_after = retry_after
        self.finished_at = now
        tr = self._tracer
        if tr is not None and tr.enabled:
            self._emit_spans(now)
        self._events.put(("done", status))
        self._done.set()

    def _emit_spans(self, fin: float) -> None:
        """The request's span tree, from the lifecycle timestamps already on
        this handle: root ``request`` = [submitted, finished]; phases
        ``queue``/``prefill``/``decode`` partition it wherever the request
        got before its terminal state. Contiguous by construction, so the
        tree is always complete and well-nested — for done, shed, expired,
        cancelled, and faulted outcomes alike."""
        tr = self._tracer
        sub, adm, pre = self.submitted_at, self.admitted_at, self.prefill_done_at
        attrs = {"id": self.rid, "outcome": self.status,
                 "tokens": len(self.tokens)}
        if self.trace_hop is not None:
            # propagated trace context: the stitched fleet trace asserts
            # hop ordering on this attr after clock-offset correction
            attrs["hop"] = self.trace_hop
        if self.error:
            attrs["error"] = self.error
        tr.add("request", self.rid, sub, fin, attrs)
        tr.add("queue", self.rid, sub, adm if adm is not None else fin, None)
        if adm is not None:
            tr.add("prefill", self.rid, adm, pre if pre is not None else fin, None)
        if pre is not None:
            tr.add("decode", self.rid, pre, fin, None)


@dataclasses.dataclass
class _ActiveSlot:
    handle: RequestHandle
    emitted: int = 0
    last_emit_at: Optional[float] = None


@dataclasses.dataclass
class _PrefillJob:
    """A slot mid-chunked-prefill: acquired in the PagedKVCache but not yet
    decoding. ``fill`` counts prompt tokens whose K/V are in the slot's
    rows (prefix-cache hits included); prefill completes when it reaches
    the prompt length and the slot installs into the decode set."""

    handle: RequestHandle
    fill: int = 0


def _percentiles(values: Sequence[float], qs=(50, 90, 99)) -> Dict[str, float]:
    """Nearest-rank percentiles of a host-side sample list (no numpy dance —
    sample counts are small and this must be dependency-free). ceil, not
    round: banker's rounding would make p50 of 5 samples the 2nd-smallest."""
    if not values:
        return {f"p{q}": 0.0 for q in qs}
    ordered = sorted(values)
    out = {}
    for q in qs:
        rank = max(0, min(len(ordered) - 1, math.ceil(q / 100 * len(ordered)) - 1))
        out[f"p{q}"] = ordered[rank]
    return out


def _sample_tail_impl(sampling, last_logits, gen_mask, rngs):
    """The sampling half of the decode tick: sample every slot from its
    own rng chain. Each row reproduces the single-request loop
    bit-for-bit: the rng split order and the [1, V] sample shapes match
    ``generate()`` with B=1, so a slot's trajectory is independent of its
    neighbors."""
    split = jax.vmap(jax.random.split)(rngs)  # [S, 2, 2]
    rngs, subs = split[:, 0], split[:, 1]

    def sample_row(key, logits_row, mask_row):
        return sample_token(key, logits_row[None], sampling, mask_row[None])[0]

    token = jax.vmap(sample_row)(subs, last_logits, gen_mask)  # [S]
    newly = jax.nn.one_hot(token, gen_mask.shape[1], dtype=jnp.bool_)
    return token, gen_mask | newly, rngs


def _forward_only_impl(model, params, token, cache, decoding=None):
    """The forward half of the decode tick: one fused model apply + the
    per-slot non-finite guard (the training anomaly predicate inlines
    here) so the healthy path pays one dispatch per tick, not two, and the
    [S] mask rides the same device_get as the tokens.

    ``decoding`` is ``[S]`` bool: the rows whose token someone is waiting
    for. The engine of a model with recurrent state passes it and the model
    advances the state of those rows alone. A dropless routed model's
    engine passes it and gets also the routed layers' ``expert_counts`` of
    this apply, summed over those rows and reduced here to three int32: (row, expert) pairs routed, the
    busiest expert's rows summed over the layers, and the (layer, expert)
    pairs that took any row, among the experts the model HOLDS (all of
    them but under ``moe_experts_held``: what its grouped matmuls read)."""
    counts = decoding is not None and model.cfg.moe_dispatch == "dropless"
    # a model with recurrent state is told which rows decode: the others
    # (parked, mid-prefill) keep their state bit for bit
    told = {"valid": decoding.astype(jnp.int32)} if model.cfg.recurrent else {}
    logits, vars_out = model.apply(
        {"params": params, "cache": cache}, token[:, None],
        mutable=["cache", "routing"] if counts else ["cache"], **told,
    )
    new_logits = logits[:, -1, :].astype(jnp.float32)
    routing = None
    if counts:
        load = jnp.stack([
            jnp.sum(jnp.where(decoding[:, None], counts, 0), axis=0)
            for counts in jax.tree.leaves(vars_out["routing"])
        ])  # [routed layers, n_experts]
        held = model.cfg.moe_experts_held
        mine = load if held is None else load[:, :held]
        routing = jnp.stack([
            jnp.sum(load), jnp.sum(jnp.max(load, axis=1)), jnp.sum(mine > 0)
        ]).astype(jnp.int32)
    return new_logits, vars_out["cache"], nonfinite_rows(new_logits), routing


def _fused_step_impl(
    model, sampling, params, last_logits, cache, gen_mask, rngs, decoding=None
):
    """One decode tick as ONE program: the sampling tail, then the fused
    forward over the tokens it drew. The last output is
    ``_forward_only_impl``'s routing counts, None without ``decoding``."""
    token, gen_mask, rngs = _sample_tail_impl(sampling, last_logits, gen_mask, rngs)
    new_logits, cache, bad, routing = _forward_only_impl(
        model, params, token, cache, decoding
    )
    return token, new_logits, cache, gen_mask, rngs, bad, routing


def _traced_anew(fn):
    """``fn`` under another identity and its own name. jax keys a trace by
    the function under the jit, so a caller that has patched what the trace
    reads (a test's broken control) gets a trace of its own; the name stays
    because a capture's readers find a program by its XLA module's."""
    @functools.wraps(fn)
    def again(*args):
        return fn(*args)
    return again


def _jit_fused_step(fresh: bool = False):
    """The decode step as the engine jits it (``fresh``: ``_traced_anew``)."""
    fn = _traced_anew(_fused_step_impl) if fresh else _fused_step_impl
    return jax.jit(fn, static_argnums=(0, 1), donate_argnums=(3, 4, 5, 6))


# one process-wide compiled step shared by every engine (warmup engines in
# benches pre-pay compiles for the measured engine); a breaker rebuild swaps
# in a PRIVATE _jit_fused_step() so a suspect executable is never reused
_FUSED_SHARED = _jit_fused_step()


# Rows of the chunk-prefill program (clipped to n_slots). Under steady load
# nearly every prefill tick advances ONE slot and a few advance two (chat
# cell: 95% / 4.5% of dispatches; PERF.md section 6, PR 32), so the program
# is sized for those, and a tick with more slots prefilling dispatches it
# again. One row count, because each one costs a trace of the whole model
# at start-up (2.2 s for the looped 2.6B model on the benchmark's host,
# 10% of that cell's set-up), and two rows, because a second row costs a
# tenth of the first (the program's fixed part, one read of the weights, is
# most of it) while a second dispatch costs it all again. Measured when the
# fixed part also held an undonated copy of the pool (gone since PR 34): the
# row count is due a re-measurement, PERF.md section 5 has the new split.
PREFILL_ROWS = 2


def _paged_chunk_prefill_impl(
    model, params, cache, tokens, starts, true_lens, rows, table, index_after
):
    """One prefill chunk for the ``R`` rows it is handed, written through
    each row's block table into the page pool: the ``[R, C]`` program at
    the heart of chunked prefill + batched admission. The engine hands
    over slots that prefill this tick and no others, ``PREFILL_ROWS`` at a
    time, the last dispatch padded.

    ``rows [R]`` are the slot ids; ``tokens``, ``starts`` and ``true_lens``
    are per ROW. ``tokens`` holds the prompt window at global positions
    ``[starts, starts + C)`` (zero-padded past the prompt; the host clamps
    ``starts`` to ``cache_len - C`` and re-sends earlier tokens in the
    window, whose K/V recompute bit-identically). The model's per-slot
    decode path does the rest: vector cache index = per-row write offset,
    per-row RoPE/ALiBi positions, causal masking against ``q_offset`` so
    real query positions never attend to the window's padded tail.

    The apply sees a cache STAGED to the R rows: the table leaf is
    ``table[rows]`` and the index leaves are ``starts`` (the pools have no
    row axis and are shared), so slots that do not prefill are not in the
    program at all. A padded entry carries the row id ``n_slots``: its
    table row reads as zeros (the TRASH page takes its writes), its start
    and tokens are zero and its logits row is dropped. Afterwards the
    table and index leaves are written back at their whole ``[n_slots]``
    shapes from ``table`` (the authoritative host mirror; the apply never
    mutates it) and ``index_after``: the host knows every row's true
    cursor (fill for prefilling rows, prompt + emitted for decoding rows,
    0 for parked). A model's recurrent state (``STATE_LEAVES``,
    ``[n_layers, n_slots, ...]``) is staged to the R rows likewise, a first
    chunk's (``starts`` 0) as zeros; the model is told each row's count of
    REAL tokens in the window (``valid``: the padded tail leaves the state
    as it was), and the rows' new state goes back into their slots. For
    such a model the host never re-sends a token (``cache_len`` is a whole
    number of chunks, so no window is clamped).

    The engine jits this with the cache DONATED (``_jit_paged_chunk``):
    every pool and state leaf aliases its output, so the rows' pages are
    scattered and the rows' state set in place, and no pool-sized value is
    made. What a fault of the dispatch then costs is ``_prefill_tick``'s
    rule.

    Returns ``(cache, last_logits, touched)`` where ``last_logits`` is
    ``[n_slots, V]`` whatever ``R`` (one shape for everything that
    installs from it): ``last_logits[s]`` is the f32 logits row at the
    prompt's final position for a slot ``s`` among ``rows`` and zeros
    elsewhere, meaningful only for rows whose prefill completes in this
    chunk (``true_lens`` falls inside the window); the engine installs
    exactly those rows. ``touched`` (None but for a dropless routed model)
    counts the (layer, expert) pairs among the held experts that took any
    of the ``R * C`` rows the program computes: what its grouped matmuls
    were handed."""
    R, C = tokens.shape
    S = table.shape[0]
    counts = model.cfg.moe_dispatch == "dropless"
    staged_table = table.at[rows].get(mode="fill", fill_value=0)
    # recurrent state: each row's REAL tokens in the window move it, the
    # padded tail does not (a padded entry has none)
    told = {"valid": jnp.clip(true_lens - starts, 0, C)} if model.cfg.recurrent else {}

    def pre(path, leaf):
        name = _leaf_name(path)
        if name == TABLE_LEAF:
            shape = leaf.shape[:-2] + staged_table.shape
            return jnp.broadcast_to(staged_table, shape).astype(leaf.dtype)
        if name in INDEX_LEAVES:
            shape = leaf.shape[:-1] + (R,)
            return jnp.broadcast_to(starts, shape).astype(leaf.dtype)
        if name in STATE_LEAVES:
            # [n_layers, n_slots, ...] -> the R rows' state; a prompt's
            # FIRST chunk reads zeros in place of what the slot's last
            # request left (there is no reset program)
            mine = leaf.at[:, rows].get(mode="fill", fill_value=0)
            fresh = (starts == 0).reshape((1, R) + (1,) * (leaf.ndim - 2))
            return jnp.where(fresh, jnp.zeros((), leaf.dtype), mine)
        return leaf

    staged = jax.tree_util.tree_map_with_path(pre, cache)
    logits, vars_out = model.apply(
        {"params": params, "cache": staged}, tokens,
        mutable=["cache", "routing"] if counts else ["cache"], **told,
    )
    touched = None
    if counts:
        held = model.cfg.moe_experts_held
        touched = sum(
            jnp.sum(jnp.sum(c if held is None else c[:, :held], axis=0) > 0)
            for c in jax.tree.leaves(vars_out["routing"])
        ).astype(jnp.int32)

    last = jax.vmap(
        lambda row, i: jax.lax.dynamic_slice_in_dim(row, i, 1, axis=0)[0]
    )(logits, jnp.clip(true_lens - 1 - starts, 0, C - 1)).astype(jnp.float32)
    last = jnp.zeros((S, last.shape[1]), jnp.float32).at[rows].set(last, mode="drop")

    def post(path, before, after):
        name = _leaf_name(path)
        if name == TABLE_LEAF:
            return jnp.broadcast_to(table, before.shape).astype(before.dtype)
        if name in INDEX_LEAVES:
            return jnp.broadcast_to(index_after, before.shape).astype(before.dtype)
        if name in STATE_LEAVES:
            # the rows' new state back into their slots; a padded entry
            # (row id n_slots) writes nothing
            return before.at[:, rows].set(after, mode="drop")
        return after

    new_cache = jax.tree_util.tree_map_with_path(post, cache, vars_out["cache"])
    return new_cache, last, touched


def _jit_paged_chunk(fresh: bool = False):
    """The chunk-prefill program as the engine jits it: the cache
    (positional 2, after the static model) donated, like the decode step's
    (``fresh``: ``_traced_anew``). Not wrapped in a lambda: a capture's
    readers find the program as the XLA module
    ``jit__paged_chunk_prefill_impl``."""
    fn = _traced_anew(_paged_chunk_prefill_impl) if fresh else _paged_chunk_prefill_impl
    return jax.jit(fn, static_argnums=(0,), donate_argnums=(2,))


# shared like _FUSED_SHARED: the static (model structure) compares equal
# across engines, so warmup engines pre-pay this compile too. ONE compiled
# program per (n_slots, chunk) whatever the prompt-length mix and however
# many slots prefill at once.
_PAGED_CHUNK_SHARED = _jit_paged_chunk()


def _spec_step_impl(
    model, sampling, K, params, last_logits, cache, gen_mask, rngs, draft,
    veto, active
):
    """Speculative fused step: sample one token per row (exactly as the
    plain step would), then VERIFY ``K`` host-proposed draft tokens for
    every row in the same single forward — the decode tick emits
    ``1 + n_acc`` tokens per slot instead of 1, at one dispatch.

    Acceptance per the standard draft-and-verify rule (Leviathan et al.
    2211.17192), specialized to the deterministic (point-mass) drafts the
    n-gram proposer emits:

    - greedy: a draft survives iff it equals the model's own processed
      argmax given the verified prefix — the emitted sequence is the plain
      greedy sequence BY CONSTRUCTION (bit-identical; tested);
    - sampling: draft ``d`` at position ``j`` is accepted with probability
      ``p_j(d)`` (its probability under the processed target
      distribution). On rejection nothing further is emitted this tick and
      ``d`` is returned as the row's VETO: the next tick's sample masks it
      out after processing, which is exactly the residual distribution
      ``norm(max(p - q, 0))`` for a point-mass ``q`` — so the emitted
      process remains distributed as plain sampling.

    Carry contract: ``last_logits[s]`` is always the model's distribution
    AFTER consuming everything row ``s`` has emitted — the accepted prefix
    advances it K-for-free, a rejection leaves it at the rejection point.
    The cache index rewinds in-graph to the consumed length (vector index:
    per-row rewind is native); rows not actively decoding (``active``
    False: parked or mid-prefill) restore their pre-tick cursor exactly.
    Requires ``sampling.repetition_penalty == 1.0`` (enforced by the
    engine): the penalty would make in-block positions interdependent.
    """
    S = last_logits.shape[0]
    V = last_logits.shape[1]
    split = jax.vmap(jax.random.split)(rngs)  # [S, 2, 2]
    rngs, subs = split[:, 0], split[:, 1]
    # two independent keys per row: the token sample and the K accept draws
    sub2 = jax.vmap(jax.random.split)(subs)
    k_tok, k_acc = sub2[:, 0], sub2[:, 1]

    arangeV = jnp.arange(V)

    def sample_row(key, logits_row, mask_row, veto_row):
        # mirror of the plain step's sample_row (same [1, V] processed
        # shapes), plus the rejection-rule veto masked AFTER processing;
        # veto = -1 matches nothing. Greedy is veto-neutral by construction
        # (the veto was rejected precisely because it is not the argmax).
        proc = process_logits(logits_row[None], sampling, mask_row[None])
        proc = jnp.where(arangeV[None, :] == veto_row, NEG_INF, proc)
        if sampling.greedy:
            return jnp.argmax(proc, axis=-1).astype(jnp.int32)[0]
        return jax.random.categorical(key, proc, axis=-1).astype(jnp.int32)[0]

    token = jax.vmap(sample_row)(k_tok, last_logits, gen_mask, veto)  # [S]
    x = jnp.concatenate([token[:, None], draft], axis=1)  # [S, K+1]
    logits, vars_out = model.apply(
        {"params": params, "cache": cache}, x, mutable=["cache"]
    )
    cache = vars_out["cache"]
    logits32 = logits.astype(jnp.float32)  # [S, K+1, V]

    flat = logits32.reshape(S * (K + 1), V)
    if sampling.greedy:
        y = jax.vmap(
            lambda row: jnp.argmax(
                process_logits(row[None], sampling, None), axis=-1
            ).astype(jnp.int32)[0]
        )(flat).reshape(S, K + 1)
        ok = (draft == y[:, :K]).astype(jnp.int32)
    else:
        p = jax.vmap(
            lambda row: jax.nn.softmax(
                process_logits(row[None], sampling, None), axis=-1
            )[0]
        )(flat).reshape(S, K + 1, V)
        p_draft = jnp.take_along_axis(
            p[:, :K, :], draft[..., None], axis=-1
        )[..., 0]  # [S, K]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (K,)))(k_acc)
        ok = (u < p_draft).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)  # [S] in [0, K]

    rows = jnp.arange(S)
    # distribution after the last ACCEPTED token — next tick samples from it
    new_logits = logits32[rows, n_acc]
    rejected = draft[rows, jnp.clip(n_acc, 0, K - 1)]
    new_veto = jnp.where(n_acc < K, rejected, -1)
    new_veto = jnp.where(active, new_veto, veto)

    n_emit = 1 + n_acc  # token + accepted drafts
    emitted = jnp.arange(K + 1)[None, :] < n_emit[:, None]  # [S, K+1]
    newly = jnp.any(
        jax.nn.one_hot(x, V, dtype=jnp.bool_) & emitted[..., None], axis=1
    )
    gen_mask = gen_mask | (newly & active[:, None])

    # rewind: the apply advanced every index leaf by K+1; the consumed
    # length is 1 + n_acc for decoding rows, 0 for everyone else (parked
    # and mid-prefill rows restore their pre-tick cursor bit-exactly)
    delta = jnp.where(active, n_emit - (K + 1), -(K + 1)).astype(jnp.int32)

    def rewind(path, leaf):
        if _leaf_name(path) in INDEX_LEAVES:
            return leaf + delta  # [..., S] + [S]: broadcasts from the right
        return leaf

    cache = jax.tree_util.tree_map_with_path(rewind, cache)
    # a non-finite ANYWHERE in the verify block poisons the row: drafts
    # "validated" by garbage logits must not be emitted (the host clamps a
    # bad row to its first token, which was sampled from the PREVIOUS
    # finite distribution — the plain step's exact guarantee)
    bad = nonfinite_rows(logits32)
    return x, n_acc, new_logits, cache, gen_mask, rngs, new_veto, bad


def _jit_spec_step():
    return jax.jit(
        _spec_step_impl, static_argnums=(0, 1, 2), donate_argnums=(4, 5, 6, 7, 9)
    )


# shared across engines like _FUSED_SHARED (statics: model, sampling, K);
# a breaker rebuild swaps in a private instance, same as the plain step
_SPEC_SHARED = _jit_spec_step()


def _host_key(seed: int) -> np.ndarray:
    """``jax.random.key_data(jax.random.PRNGKey(seed))``, bit for bit, made
    on the host with no device launch. The threefry seed is the seed's
    two's-complement words (high, low); 32-bit JAX first wraps the seed to
    int32, so its high word is 0. A seed outside int64 raises as
    ``PRNGKey`` does; another PRNG implementation asks the device."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
    word = (int(np.int64(seed)) + jax.config.jax_random_seed_offset) % 2**64
    high = word >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([high, word & 0xFFFFFFFF], np.uint32)


@jax.jit
def _install_rows(last_logits, gen_mask, rngs, veto, mask, logits_rows, keys):
    """Install every completed prefill in ONE dispatch: rows under ``mask``
    get their prefill logits, a cleared penalty mask, a fresh rng chain
    and no vetoed draft; other rows pass through untouched. ``mask`` and
    ``keys`` come from the host, so an admission is this one launch
    whatever the slot count."""
    m = mask[:, None]
    return (
        jnp.where(m, logits_rows, last_logits),
        jnp.where(m, jnp.zeros_like(gen_mask), gen_mask),
        jnp.where(m, keys, rngs),
        jnp.where(mask, -1, veto),
    )


@jax.jit
def _install_import(last_logits, gen_mask, rngs, veto, slot, row, mask_row,
                    key, veto_val):
    """Install ONE imported stream's decode carry (migration receive): the
    exact last_logits/gen_mask/rng/veto the source exported, at the
    destination slot — the continuation is bit-identical to the source
    having kept decoding."""
    zero = jnp.int32(0)
    return (
        jax.lax.dynamic_update_slice(last_logits, row[None], (slot, zero)),
        jax.lax.dynamic_update_slice(gen_mask, mask_row[None], (slot, zero)),
        jax.lax.dynamic_update_slice(rngs, key[None], (slot, zero)),
        jax.lax.dynamic_update_slice(veto, veto_val[None], (slot,)),
    )


class ServingEngine:
    """Slot-scheduled continuous batching over one jitted decode step.

    Sampling semantics (temperature/top-k/top-p/penalty/greedy) are
    ENGINE-level: they are static arguments baked into the compiled fused
    step, so per-request variation would recompile per combination.
    Requests carry what is cheap to vary: prompt, token budget, seed,
    deadline.
    """

    def __init__(
        self,
        cfg,
        params: Any,
        n_slots: int = 4,
        cache_len: Optional[int] = None,
        sampling: SamplingConfig = SamplingConfig(),
        eos_token_id: Optional[int] = None,
        max_queue: int = 64,
        mesh=None,
        metrics=None,
        metrics_interval: int = 0,
        clock: Callable[[], float] = time.monotonic,
        breaker_threshold: int = 3,
        breaker_cooldown: int = 1,
        max_rebuilds: int = 3,
        shed_warmup: int = 8,
        itl_decay: float = 0.9,
        chaos=None,
        prefill_chunk: int = 64,
        prefix_cache_chunks: int = 256,
        max_prefill_buckets: int = 8,
        kv_layout: str = "paged",
        page_size: int = 16,
        page_pool_tokens: int = 0,
        draft_k: int = 0,
        draft_fn: Optional[Callable[[Sequence[int], int], List[int]]] = None,
        fused_tail: bool = True,
        role: str = "mixed",
        page_shipper: Optional[Callable[..., None]] = None,
        obs_dir: Optional[str] = None,
        trace: bool = True,
        trace_capacity: int = 8192,
        flight_capacity: int = 256,
        qos=None,
        emit_buffer_max: int = 1024,
        tenant_buckets_capacity: int = 4096,
    ):
        # Retired arguments. benchmark/drivers/serve_open_loop.py passes its
        # traffic file's "engine" block as keywords, and
        # benchmark/traffic/alpaca_open_poisson{,_ouro}.json carry
        # max_prefill_buckets, kv_layout and fused_tail; only a benchmark
        # PR may edit those files. The names are accepted and select
        # nothing; they go when such a PR drops the three keys.
        del max_prefill_buckets
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout={kv_layout!r} was removed: the KV page pool is "
                "the only cache layout (drop the argument)"
            )
        if not fused_tail:
            raise ValueError(
                "fused_tail=False was removed: the fused step is the only "
                "decode program (drop the argument)"
            )
        self.cfg = cfg
        self.cache_len = cache_len or cfg.max_seq_len
        if prefill_chunk < 1:
            raise ValueError(
                "prefill_chunk must be >= 1: one-shot prefill "
                "(prefill_chunk=0) was removed, chunked prefill is the only "
                "admission path"
            )
        if prefix_cache_chunks < 0:
            raise ValueError("prefix_cache_chunks must be >= 0 (0 disables)")
        # a chunk larger than the cache clamps so the window math never
        # exceeds capacity
        self.prefill_chunk = min(prefill_chunk, self.cache_len)
        if draft_k < 0:
            raise ValueError("draft_k must be >= 0 (0 disables speculation)")
        if draft_k and sampling.repetition_penalty != 1.0:
            raise ValueError(
                "speculative serving (draft_k > 0) requires "
                "repetition_penalty == 1.0: the penalty makes in-block "
                "positions interdependent (one-shot generate_speculative "
                "emulates it; the batched verify step does not)"
            )
        self.draft_k = int(draft_k)
        self.draft_fn = draft_fn or ngram_propose
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        # A model with recurrent state (cfg.layer_pattern with "mamba"
        # blocks): a slot's state sits beside its K/V pages and is right
        # only at the position the slot has reached. What would need the
        # state at ANOTHER position is refused by name (docs/SERVING.md).
        self._has_state = cfg.recurrent
        self._state_bytes_per_slot = cfg.state_bytes_per_slot
        if self._has_state:
            if draft_k:
                raise ValueError(
                    "draft_k > 0 is refused for a model with recurrent "
                    "state: a rejected draft would have to roll the state "
                    "back (state_refusals: speculation)"
                )
            if self.cache_len % self.prefill_chunk:
                raise ValueError(
                    f"cache_len ({self.cache_len}) must be a multiple of "
                    f"prefill_chunk ({self.prefill_chunk}) for a model with "
                    "recurrent state: a clamped last window re-sends tokens, "
                    "and a recurrence that sees a token twice is wrong"
                )
            if role != "mixed":
                raise ValueError(
                    "role must be 'mixed' for a model with recurrent state: "
                    "a page span without its state is half a request "
                    "(state_refusals: page_span)"
                )
        if role == "prefill" and draft_k:
            raise ValueError(
                "role='prefill' replicas never decode; draft_k must be 0"
            )
        self.role = role
        # the ship seam: callable(payload, target_url, on_done) — provided
        # by the serving front end (HTTP POST to <target>/ingest off the
        # tick thread) or a test harness (direct import into a peer
        # engine). on_done(None) confirms; on_done(err_str) fails the
        # migration retryably (the source stream falls back to recompute).
        self.page_shipper = page_shipper
        self.page_size = int(page_size)
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.cache_len % page_size:
            raise ValueError(
                f"page_size ({page_size}) must divide cache_len "
                f"({self.cache_len})"
            )
        if self.prefill_chunk % page_size:
            raise ValueError(
                f"page_size ({page_size}) must divide prefill_chunk "
                f"({self.prefill_chunk}): chunk-aligned prefix sharing "
                "must be page-aligned so divergence starts on a page "
                "boundary (no live page is ever written by two rows)"
            )
        if page_pool_tokens == 0:
            page_pool_tokens = n_slots * self.cache_len
        if page_pool_tokens % page_size:
            raise ValueError(
                f"page_pool_tokens ({page_pool_tokens}) must be a "
                f"multiple of page_size ({page_size})"
            )
        self.page_pool_tokens = int(page_pool_tokens)
        n_pages = page_pool_tokens // page_size + 1  # + trash page
        self.model = decode_model(
            cfg, self.cache_len, kv_pages=(n_pages, page_size)
        )
        self.sampling = sampling
        self.eos_token_id = eos_token_id
        self.mesh = mesh
        self.now = clock
        self.metrics = metrics
        self.metrics_interval = metrics_interval

        self.n_slots = n_slots
        self.slots = PagedKVCache(self.model, n_slots, mesh=mesh)
        V = cfg.vocab_size
        self._last_logits = jnp.zeros((n_slots, V), jnp.float32)
        self._gen_mask = jnp.zeros((n_slots, V), jnp.bool_)
        self._rngs = jnp.stack([jax.random.PRNGKey(0)] * n_slots)
        # rejection-rule carry: the draft token the verify step rejected
        # last tick, masked out of this tick's sample (-1 = none)
        self._veto = jnp.full((n_slots,), -1, jnp.int32)
        self._active: List[Optional[_ActiveSlot]] = [None] * n_slots
        # slot -> _PrefillJob for slots mid-chunked-prefill (acquired, not
        # yet decoding); only the tick thread touches it
        self._prefilling: Dict[int, _PrefillJob] = {}
        # a banked page is reusable only with the state AT its boundary:
        # a model with recurrent state gets no prefix index (said once, in
        # the prefix_cache_refused event at the end of construction)
        self._prefix_cache_chunks = 0 if self._has_state else prefix_cache_chunks
        self._prefix_cache: Optional[PagedPrefixIndex] = self._make_prefix_cache()
        # (the name the dispatch calls: declared to the donation-safety rule)
        # graftlint: donates[2]
        self._paged_chunk = _PAGED_CHUNK_SHARED
        self._spec = _SPEC_SHARED
        # rows of the chunk-prefill program: the slots that prefill in a
        # tick go through it this many at a time
        self.prefill_rows = min(PREFILL_ROWS, n_slots)
        # compile-family sanitizer (analysis/runtime.py): each labeled jit
        # dispatch site declares the number of distinct cache signatures it
        # may legitimately produce over this engine's lifetime. The fixed-
        # shape discipline says ONE each — the fused decode step, the
        # [R, C] chunk prefill, and the K-draft verify are all single
        # programs whatever the occupancy/prompt mix. A second signature
        # means some per-request axis leaked into a shape or static
        # (strict mode raises listing the signatures; production warns).
        self._ds_decode = bounded_dispatch("engine.decode_step", 1)
        self._ds_prefill = bounded_dispatch("engine.prefill_chunk", 1)
        self._ds_spec = bounded_dispatch("engine.spec_verify", 1)
        # the paged-attention kernel's per-tick signature (table/pool/offset
        # shapes — the kernel itself runs INSIDE the decode/spec program, so
        # this site pins the host-visible inputs that select its compiled
        # family)
        self._ds_paged = bounded_dispatch("engine.paged_attention", 1)
        # is the paged-attention kernel compiled into the decode program?
        # Same gate the model consults, so the exported gauge can never
        # disagree with what actually traced.
        from zero_transformer_tpu.ops.attention import (
            latent_kernel_supported, paged_kernel_supported,
        )

        window = dict(
            T=1 + self.draft_k if self.draft_k else 1, H=cfg.n_heads,
            S=self.cache_len, page_size=self.page_size,
            dtype=resolve_dtype(cfg.compute_dtype),
        )
        if cfg.latent_attention:  # the latent kernel reads the latent pages
            self._paged_kernel = latent_kernel_supported(
                cfg.attention_impl, R=cfg.latent_row, **window
            )
        else:
            self._paged_kernel = paged_kernel_supported(
                cfg.attention_impl, KVH=cfg.kv_heads, D=cfg.head_width, **window
            )
        # a dropless routed model's decode step also counts what its
        # routed layers sent where (``_forward_only_impl``)
        self._counts_routing = cfg.moe_dispatch == "dropless"
        # the rows that decode, on the device: what a routed model's counts
        # are summed over and what a model with recurrent state advances
        self._sends_decoding = self._counts_routing or self._has_state
        self._decoding: Tuple[Optional[list], Any] = (None, None)
        # ... and so does its chunk-prefill program: the last chunk's count,
        # on the device until a decode tick's device_get takes it along
        self._prefill_touched = None
        # the last chunk program's logits rows: ready when it has run
        self._chunk_in_flight = None
        # did THIS tick run a prefill chunk? classifies the tick's ITL
        # samples for attribution
        self._prefill_work = False

        # disaggregation / migration state (tick thread owns placement;
        # other threads only enqueue under the lock)
        self._pending_imports: deque = deque()  # (handle, payload)
        self._migrate_requests: Dict[str, str] = {}  # rid (or "*") -> target
        self._migrating: Dict[int, RequestHandle] = {}  # awaiting ship ack
        self._migrations_in_flight = 0

        # overload isolation (PR 18): the declared class policy (inert
        # defaults when no config — no floors, unlimited buckets), the
        # per-(tenant, class) admission buckets, and the admission queue
        # as per-class deficit-weighted round-robin priced in work tokens
        self.qos = (
            qos if isinstance(qos, QosPolicy) else QosPolicy.from_config(qos)
        )
        self._tenant_buckets = TenantBuckets(
            self.qos, capacity=tenant_buckets_capacity
        )
        self.emit_buffer_max = max(1, int(emit_buffer_max))
        self._queue: ClassQueue = self._make_queue()
        # brownout rung in force on THIS replica (the router's fleet
        # controller pushes transitions via POST /admin/brownout; an
        # engine-local set_brownout serves single-replica deployments)
        self._brownout_rung = BROWNOUT_RUNGS[0]
        self.max_queue = max_queue
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._tick = 0
        self._dead: Optional[str] = None  # set by _abort; submit() fails fast

        # resilience state (serving/resilience.py primitives)
        self.lifecycle = Lifecycle(clock)
        self._breaker = CircuitBreaker(breaker_threshold, breaker_cooldown)
        self.max_rebuilds = max_rebuilds
        # consecutive-incident rebuild budget: resets when the breaker
        # closes, so a long-lived replica isn't killed by its lifetime
        # trip COUNT after recovering cleanly from each incident
        self._rebuilds_since_recovery = 0
        self._itl_ewma = ItlEwma(decay=itl_decay, warmup=shed_warmup)
        self._chaos = chaos
        self._fused = _FUSED_SHARED  # swapped for a private jit on rebuild
        # staged by reload_params as (serving tree, its byte gauges,
        # swap-event); swapped at tick
        self._pending_params = None
        self._last_reload_event: Optional[threading.Event] = None
        self._drain_deadline: Optional[float] = None
        self._drain_started: Optional[float] = None
        self.drain_latency_s: Optional[float] = None

        # serving counters / latency samples (host side)
        self.stats: Dict[str, Any] = {
            "submitted": 0,
            "completed": 0,
            "rejected_queue_full": 0,
            "rejected_invalid": 0,
            "expired_queued": 0,
            "expired_decoding": 0,
            "cancelled": 0,
            "tokens_out": 0,
            "peak_occupancy": 0,
            "peak_queue_depth": 0,
            # resilience counters (exported via /metrics and logged as
            # MetricsLogger events so serving incidents land in the same
            # JSONL timeline the training stack writes)
            "tick_faults": 0,
            "poisoned_slots": 0,
            "breaker_trips": 0,
            "shed_infeasible": 0,
            "rejected_draining": 0,
            "drain_forced": 0,
            "reloads": 0,
            "reloads_rejected": 0,
            # prefill-path counters (chunked prefill / prefix cache)
            "prefill_chunks": 0,
            "prefill_faults": 0,
            "prefill_faults_escalated": 0,
            "expired_prefilling": 0,
            # rows of the chunk-prefill program, summed over its dispatches:
            # the slots that prefilled and the rows it computed (live /
            # computed is the share of the program that was not padding)
            "prefill_rows_live": 0,
            "prefill_rows_computed": 0,
            # paged-KV counters: allocation pressure (a page fault = the
            # pool was empty and prefix-cache pages had to be reclaimed),
            # and the preemption of last resort when even reclaim failed
            "page_faults": 0,
            "pages_reclaimed": 0,
            "preemptions": 0,
            # admission attempts deferred for PAGES while a slot was free
            # (the request went back to the queue's head): the pool, not
            # the slot count, was what admission ran out of
            "page_waits": 0,
            # model passes run by decode ticks: ticks x cfg.n_loops
            "loop_passes": 0,
            # what the paged decode kernel walks, summed over decode ticks
            # from the host's table mirror: the pages of the rows' live
            # extents (a row that maps none is a one-page row to the
            # kernel), and the whole table it is handed
            "kernel_pages_live": 0,
            "kernel_pages_table": 0,
            # a dropless routed model's decode ticks, summed over ticks and
            # routed layers: (row, expert) pairs routed, the busiest
            # expert's rows, the mean rows an expert (pairs / n_experts)
            "moe_tokens_routed": 0,
            "moe_expert_load_max": 0,
            "moe_expert_load_mean": 0.0,
            # a model with recurrent state: first chunks (a slot's state
            # read as zeros), the slots decode ticks' state update did not
            # move (summed over ticks), and what was refused, by reason
            "state_resets": 0,
            "state_rows_skipped": 0,
            "state_refusals_prefix_cache": 0,
            "state_refusals_page_span": 0,
            # speculation counters: acceptance_rate = accepted / drafted
            "spec_ticks": 0,
            "draft_tokens": 0,
            "accepted_tokens": 0,
            # disaggregation / live migration counters: streams shipped out
            # (prefill handoffs + live migrations), streams imported, ship
            # failures (the source stream then fails retryably and the
            # router falls back to re-dispatch-and-recompute), and prefill
            # handoffs specifically (the disagg split of migrations_out)
            "migrations_out": 0,
            "migrations_in": 0,
            "migration_failures": 0,
            "prefill_handoffs": 0,
            # overload-isolation counters (PR 18): per-tenant bucket
            # rejections, queue-full sheds that evicted a LOWER class to
            # keep a higher one, preemptions of running lower-class
            # streams for a waiting higher class, brownout admission
            # rejections + rung transitions, and streams finished because
            # their SSE consumer stalled past the emit-buffer bound
            "rejected_quota": 0,
            "rejected_brownout": 0,
            "shed_lower_class": 0,
            "preempted_for_class": 0,
            "brownout_transitions": 0,
            "stalled_streams": 0,
            # pinned 0 BY CONSTRUCTION: an imported stream installs its
            # shipped pages and never runs prefill for consumed positions
            # (asserted via dest prefill_chunks == 0 in the parity tests).
            # The O(tokens) cost of the recompute fallback is counted on
            # the ROUTER (resume_replayed_tokens) — the replica can't
            # distinguish a resumed-as-prompt request from a long prompt.
            "import_replayed_tokens": 0,
        }
        # observability (obs/): span tracer, Prometheus registry, flight
        # recorder, on-demand profiler. Latency samples land in FIXED-BUCKET
        # histograms — a /metrics read is an O(buckets) walk that never
        # takes the scheduler lock (the pre-PR7 deques made every snapshot
        # sort the 10k-sample history under it)
        self.obs_dir = str(obs_dir) if obs_dir else None
        self.tracer = Tracer(enabled=trace, capacity=trace_capacity, clock=clock)
        self.registry = Registry()
        self.flight = FlightRecorder(
            directory=self.obs_dir, capacity=flight_capacity,
            tracer=self.tracer, clock=clock,
        )
        self._profiler = ProfileWindow(self.obs_dir, prefix="serve")
        # The engine holds the SERVING form of the weights
        # (inference.serving_params), never the tree it was given: what a
        # reload is validated against is the source form's shapes and
        # dtypes, no bytes.
        self._source_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
        )
        self.params, self._weights_bytes = self._prepare_weights(params)
        self._h_ttft = self.registry.histogram(
            "serve_ttft_seconds",
            "Submit-to-first-token latency (queue wait included)",
            LATENCY_BUCKETS,
        )
        self._h_itl = self.registry.histogram(
            "serve_itl_seconds", "Inter-token latency, all decode ticks",
            LATENCY_BUCKETS,
        )
        # ITL samples from ticks that did NO prefill work — the pure-decode
        # floor; the gap between itl and itl_decode percentiles IS the
        # prefill interference the chunk budget exists to bound
        self._h_itl_decode = self.registry.histogram(
            "serve_itl_decode_seconds",
            "Inter-token latency on ticks with no prefill work (decode floor)",
            LATENCY_BUCKETS,
        )
        self._h_queue_wait = self.registry.histogram(
            "serve_queue_wait_seconds", "Submit-to-slot-admission wait",
            LATENCY_BUCKETS,
        )
        self._h_prefill = self.registry.histogram(
            "serve_prefill_seconds",
            "Admission-to-install prefill latency (prefix hits included)",
            LATENCY_BUCKETS,
        )
        # per-class latency families: the fleet aggregator merges these by
        # name, so per-class SLO objectives (qos_class on an Objective)
        # bind to `serve_ttft_seconds_<class>` with zero aggregator work
        self._h_ttft_class = {
            name: self.registry.histogram(
                f"serve_ttft_seconds_{name}",
                f"Submit-to-first-token latency, {name} class",
                LATENCY_BUCKETS,
            )
            for name in self.qos.names()
        }
        self._h_itl_class = {
            name: self.registry.histogram(
                f"serve_itl_seconds_{name}",
                f"Inter-token latency, {name} class",
                LATENCY_BUCKETS,
            )
            for name in self.qos.names()
        }
        # legacy attribute names: tests and older callers measured the
        # latency deques by len(); Histogram.__len__ keeps that contract
        self._ttft = self._h_ttft
        self._itl = self._h_itl
        self._itl_decode = self._h_itl_decode
        self._register_exports()
        self._started = self.now()
        if self._has_state and prefix_cache_chunks:
            self.stats["state_refusals_prefix_cache"] += 1
            self._event(
                "prefix_cache_refused", asked=prefix_cache_chunks,
                reason="recurrent state: a banked page is reusable only "
                       "with the state at its boundary; no index is built",
            )

    # ----------------------------------------------------- device-state build

    def _make_queue(self) -> ClassQueue:
        """The admission queue: per-class DWRR priced in work tokens (the
        same unit reservations use), classed by each request's qos."""
        return ClassQueue(
            self.qos,
            cost=lambda h: self._total_need_tokens(h.request),
            class_of=lambda h: h.request.qos,
        )

    # -------------------------------------------------------- qos / brownout

    def _class_slots_in_use(self) -> Dict[str, int]:
        """Decode + mid-prefill slots currently held, per class."""
        counts = {name: 0 for name in self.qos.names()}
        for act in self._active:
            if act is not None:
                counts[self.qos.normalize(act.handle.request.qos)] += 1
        for job in self._prefilling.values():
            counts[self.qos.normalize(job.handle.request.qos)] += 1
        return counts

    def _class_pages_in_use(self) -> Dict[str, int]:
        """KV pages RESERVED per class (the admission-time worst case —
        derivable from the handles alone, so no stateful page accounting
        can drift)."""
        counts = {name: 0 for name in self.qos.names()}
        for act in self._active:
            if act is not None:
                counts[self.qos.normalize(act.handle.request.qos)] += (
                    self.slots.blocks_for(
                        self._total_need_tokens(act.handle.request)
                    )
                )
        for job in self._prefilling.values():
            counts[self.qos.normalize(job.handle.request.qos)] += (
                self.slots.blocks_for(
                    self._total_need_tokens(job.handle.request)
                )
            )
        return counts

    def _slot_eligible(self, cls: str, in_use: Dict[str, int]) -> bool:
        """May class ``cls`` take a free slot now? Only if doing so leaves
        at least the unmet slot floors of every higher class free."""
        floors = {
            name: float(c.slot_floor) for name, c in self.qos.classes.items()
        }
        held = reserved_above(
            self.qos, cls, floors, {k: float(v) for k, v in in_use.items()}
        )
        return self.slots.free_count > held

    def _pages_reserved_above(self, cls: str) -> int:
        """Paged-pool pages held back from class ``cls`` by higher-class
        floors (page_floor_frac x total pool, minus what those classes
        already hold)."""
        total = self.slots.pool.n_pages - 1
        floors = {
            name: float(int(c.page_floor_frac * total))
            for name, c in self.qos.classes.items()
        }
        if not any(floors.values()):
            return 0
        in_use = {
            k: float(v) for k, v in self._class_pages_in_use().items()
        }
        return int(reserved_above(self.qos, cls, floors, in_use))

    @property
    def brownout_rung(self) -> str:
        return self._brownout_rung

    def set_brownout(self, rung: str) -> Dict[str, Any]:
        """Apply a brownout rung (router push or operator override).
        Idempotent; every transition is a flight-recorder event and a
        counter. Rung effects at admission/dispatch time:
        ``no_spec`` disables speculative decode; ``shrink_batch``
        additionally clamps batch-class token budgets; ``suspend_batch``
        additionally rejects batch admission (retryable, class
        Retry-After)."""
        if rung not in BROWNOUT_RUNGS:
            raise ValueError(
                f"unknown brownout rung {rung!r} (rungs: {BROWNOUT_RUNGS})"
            )
        old = self._brownout_rung
        if rung != old:
            self._brownout_rung = rung
            self.stats["brownout_transitions"] += 1
            self._event("brownout_rung", old=old, new=rung)
        return {"rung": self._brownout_rung, "previous": old}

    @property
    def _spec_enabled(self) -> bool:
        return not rung_at_least(self._brownout_rung, "no_spec")

    def _maybe_preempt_for_class(self) -> None:
        """With zero free slots and a gold request waiting, preempt ONE
        running stream of the lowest active class (strictly lower-ranked
        than the waiter) — retryable finish, so the router re-dispatches
        it; the freed slot admits the gold request this same tick. The
        least-progressed victim loses the least work. Never fires across
        equal ranks, so batch-vs-batch contention stays FIFO."""
        if self.slots.free_count:
            return
        with self._lock:
            waiting = self._queue.best_waiting_rank()
        if waiting is None or waiting != 0:  # only the TOP class preempts
            return
        victim_slot, victim_rank, victim_emitted = None, -1, -1
        for slot, act in enumerate(self._active):
            if act is None:
                continue
            rank = self.qos.rank(act.handle.request.qos)
            if rank <= waiting:
                continue
            # lowest class first; among equals, least progress lost
            if rank > victim_rank or (
                rank == victim_rank and act.emitted < victim_emitted
            ):
                victim_slot, victim_rank, victim_emitted = (
                    slot, rank, act.emitted
                )
        if victim_slot is None:
            return
        now = self.now()
        victim = self._active[victim_slot]
        cls = self.qos.class_of(victim.handle.request.qos)
        victim.handle._finish(
            FAILED, now,
            error=(
                f"preempted for higher QoS class (retryable): "
                f"{cls.name} stream yielded its slot"
            ),
            retryable=True, retry_after=cls.retry_after_s,
        )
        self.stats["preempted_for_class"] += 1
        self._retire([victim_slot])
        self._event(
            "qos_preemption", victim_class=cls.name,
            emitted=victim_emitted,
        )

    def _make_prefix_cache(self) -> Optional[PagedPrefixIndex]:
        if not self._prefix_cache_chunks:
            return None
        # page-id entries refcounted against THIS pool instance — must be
        # rebuilt whenever the pool is (reload keeps the pool and only
        # flushes)
        return PagedPrefixIndex(
            self.prefill_chunk, self._prefix_cache_chunks, self.slots.pool
        )

    def _total_need_tokens(self, request: Request) -> int:
        """Worst-case cache positions the request can ever write: prompt +
        budget, plus the draft window when speculating (the verify forward
        writes K draft positions past the cursor before the rewind)."""
        return min(
            len(request.prompt) + request.max_new_tokens + self.draft_k,
            self.cache_len,
        )

    # ------------------------------------------------------------- admission

    def _validate(self, request: Request) -> Optional[str]:
        T = len(request.prompt)
        if T < 1:
            return "empty prompt"
        if request.max_new_tokens < 1:
            return "max_new_tokens must be >= 1"
        # same bound as generate()._start_decode: the final token is never
        # fed back, so the cache holds T + max_new - 1 positions
        if T + request.max_new_tokens - 1 > self.cache_len:
            return (
                f"prompt ({T}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds cache_len ({self.cache_len})"
            )
        if self.slots.blocks_for(
            self._total_need_tokens(request)
        ) > self.slots.n_pages - 1:
            # bigger than the ENTIRE pool: admission's capacity check could
            # never pass, and a FIFO queue would stall behind it forever —
            # reject at submit instead
            return (
                f"prompt ({T}) + max_new_tokens ({request.max_new_tokens}) "
                f"needs more KV pages than the whole pool holds "
                f"({self.page_pool_tokens} token positions); raise "
                f"--page-pool-tokens or lower the request"
            )
        if (
            self.draft_k
            and T + request.max_new_tokens + self.draft_k > self.cache_len
        ):
            # the verify forward writes K positions past the final cursor
            # before rewinding (mirrors generate_speculative's bound); a
            # clamped write would silently corrupt the row's tail instead
            return (
                f"prompt ({T}) + max_new_tokens ({request.max_new_tokens}) "
                f"+ draft_k ({self.draft_k}) exceeds cache_len "
                f"({self.cache_len}); lower one of them"
            )
        if (
            self.cfg.position == "learned"
            and T + request.max_new_tokens > self.cfg.max_seq_len
        ):
            return "learned positions cannot extrapolate past max_seq_len"
        if self.role == "prefill" and request.prefill_to is None:
            return (
                "this is a prefill-role replica: requests must name a "
                "decode target (prefill_to)"
            )
        return None

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 32,
        seed: int = 0,
        deadline: Optional[float] = None,
        timeout: Optional[float] = None,
        request_id: Optional[str] = None,
        prefill_to: Optional[str] = None,
        trace_hop: Optional[int] = None,
        tenant: str = "anon",
        qos: Optional[str] = None,
    ) -> RequestHandle:
        """Enqueue a request; returns its handle immediately.

        ``timeout`` (seconds from now) is sugar for an absolute ``deadline``.
        A full queue or invalid request returns a handle already finished as
        ``rejected`` (callers map that to HTTP 429 / 400) — the error string
        says which. ``request_id`` threads an inbound correlation id
        (``X-Request-Id``) through the span tree and response; omitted, one
        is generated here at admission. ``trace_hop`` is the router's hop
        index for this dispatch (``X-Trace-Hop``) — recorded on the span
        tree so the stitched fleet trace can order hops across processes.
        ``tenant``/``qos`` (``X-Tenant-Key`` / ``X-QoS-Class``) select the
        token bucket the request is charged to and the class it queues,
        sheds, and browns out as.
        """
        now = self.now()
        if timeout is not None:
            deadline = now + timeout if deadline is None else min(deadline, now + timeout)
        qos_name = self.qos.normalize(qos)
        cls = self.qos.classes[qos_name]
        max_new_tokens = int(max_new_tokens)
        if (
            rung_at_least(self._brownout_rung, "shrink_batch")
            and cls.brownout_max_new_tokens is not None
            and max_new_tokens > cls.brownout_max_new_tokens
        ):
            # brownout rung 2: the class keeps serving, on a shrunken
            # budget — graceful degradation before any admission is cut
            max_new_tokens = cls.brownout_max_new_tokens
        request = Request(
            list(prompt), max_new_tokens, int(seed), deadline,
            prefill_to=prefill_to,
            tenant=str(tenant or "anon")[:64], qos=qos_name,
        )
        handle = RequestHandle(request, next(self._ids), now, request_id=request_id)
        handle._tracer = self.tracer
        handle.trace_hop = trace_hop
        handle.emit_buffer_max = self.emit_buffer_max
        invalid = self._validate(request)
        with self._lock:
            if self._dead is not None:
                # the scheduler is gone — nothing will ever drain the queue,
                # so enqueueing would hang the caller forever (checked under
                # the lock: _abort drains the queue under the same lock)
                handle._finish(FAILED, now, error=self._dead)
                return handle
            if self.lifecycle.state == DRAINING:
                # admission is closed; in-flight generations finish, new
                # traffic belongs on another replica (server: 503 +
                # Retry-After, sized to the remaining drain window)
                self.stats["rejected_draining"] += 1
                left = (
                    max(1.0, self._drain_deadline - now)
                    if self._drain_deadline is not None
                    else 1.0
                )
                handle._finish(
                    REJECTED, now, error="server draining; retry elsewhere",
                    retryable=True, retry_after=left,
                )
                return handle
            self.stats["submitted"] += 1
            if invalid is not None:
                self.stats["rejected_invalid"] += 1
                handle._finish(REJECTED, now, error=invalid)
                return handle
            if (
                rung_at_least(self._brownout_rung, "suspend_batch")
                and self.qos.rank(qos_name) == len(self.qos.names()) - 1
            ):
                # brownout rung 3: the lowest class stops admitting
                # entirely — its budget already shrank at rung 2; now its
                # traffic waits out the overload elsewhere
                self.stats["rejected_brownout"] += 1
                handle._finish(
                    REJECTED, now,
                    error=(
                        f"brownout ({self._brownout_rung}): "
                        f"{qos_name} admission suspended; retry later"
                    ),
                    retryable=True, retry_after=cls.retry_after_s,
                )
                return handle
            quota_wait = self._tenant_buckets.take(
                request.tenant, qos_name,
                len(request.prompt) + request.max_new_tokens, now,
            )
            if quota_wait > 0:
                # the tenant's own bucket is dry — its flood is ITS
                # problem; every other tenant's admission is untouched
                self.stats["rejected_quota"] += 1
                handle._finish(
                    REJECTED, now,
                    error=(
                        f"tenant quota exhausted ({qos_name}); "
                        f"retry later"
                    ),
                    retryable=True, retry_after=quota_wait,
                )
                return handle
            if len(self._queue) >= self.max_queue:
                # queue-full pressure evicts the newest STRICTLY-lower
                # class request (retryably) before rejecting a higher one
                victim = self._queue.pop_lowest_class(
                    above_rank=self.qos.rank(qos_name)
                )
                if victim is not None:
                    vcls = self.qos.class_of(victim.request.qos)
                    self.stats["shed_lower_class"] += 1
                    victim._finish(
                        REJECTED, now,
                        error=(
                            f"queue full; shed for higher QoS class "
                            f"({vcls.name} yielded); retry later"
                        ),
                        retryable=True, retry_after=vcls.retry_after_s,
                    )
                else:
                    self.stats["rejected_queue_full"] += 1
                    handle._finish(
                        REJECTED, now,
                        error=f"queue full ({self.max_queue} waiting); retry later",
                        retryable=True, retry_after=max(1.0, cls.retry_after_s),
                    )
                    return handle
            if request.deadline is not None and infeasible_deadline(
                request.deadline, now, request.max_new_tokens,
                len(self._queue), self.n_slots, self._itl_ewma,
            ):
                # provably cannot finish in time: a fast honest 503 now
                # beats decoding tokens nobody will wait for (overload
                # degrades into sheds, not timeout storms)
                self.stats["shed_infeasible"] += 1
                handle._finish(
                    REJECTED, now,
                    error="deadline infeasible at current load (shed)",
                    retryable=True, retry_after=1.0,
                )
                return handle
            self._queue.append(handle)
            self.stats["peak_queue_depth"] = max(
                self.stats["peak_queue_depth"], len(self._queue)
            )
        return handle

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active_count(self) -> int:
        return sum(1 for a in self._active if a is not None)

    @property
    def free_pages(self) -> int:
        """Spare KV capacity: free pool pages. A fleet router reads this
        from /healthz as an admission input — "how much more can this
        replica take"."""
        return self.slots.pool.free_count

    # -------------------------------------------------------------- schedule

    def _pop_queue(
        self, eligible=None,
    ) -> Optional[RequestHandle]:
        """Pop the next admissible queued handle (DWRR-fair across QoS
        classes; ``eligible`` gates classes whose admission would eat a
        higher class's reservation floor), finishing cancelled / expired
        ones on the way; None when nothing is admissible."""
        with self._lock:
            now = self.now()
            while self._queue:
                cand = self._queue.popleft(eligible=eligible)
                if cand is None:
                    return None
                if cand._cancel.is_set():
                    self.stats["cancelled"] += 1
                    cand._finish(CANCELLED, now)
                elif cand.request.deadline is not None and now > cand.request.deadline:
                    self.stats["expired_queued"] += 1
                    cand._finish(EXPIRED, now, error="deadline expired in queue")
                else:
                    return cand
        return None

    def _admit(self) -> None:
        """Claim a slot per admissible queued request and start its chunked
        prefill. Prefix-cache hits land here: the cached pages are mapped
        into the slot's block table (refcount bumps — zero K/V bytes move),
        the chunk loop starts at the first NOVEL chunk, and the chunk
        forwards themselves happen in ``_prefill_tick``, shared across
        every mid-prefill slot — admission of N requests is one batch, not
        N prefills.

        Admission is CAPACITY-CHECKED: the request's worst case
        (prompt + budget + draft headroom, minus whatever the hit covers)
        is reserved in the page pool up front, so an admitted stream can
        never hit a mid-decode out-of-pages fault — when the pool can't
        cover it (even after reclaiming cold prefix-cache pages), the
        request WAITS at the queue head instead (``page_waits``)."""
        self._maybe_preempt_for_class()
        while self.slots.free_count:
            in_use = self._class_slots_in_use()
            handle = self._pop_queue(
                eligible=lambda c: self._slot_eligible(c, in_use)
            )
            if handle is None:
                return
            if not self._paged_admission_fits(handle):
                # back at the HEAD: admission stays FIFO, and the next
                # retirement frees the pages this request is waiting for
                self.stats["page_waits"] += 1
                with self._lock:
                    self._queue.appendleft(handle)
                return
            slot = self.slots.acquire()
            fill = 0
            try:
                if self._prefix_cache is not None:
                    fill, hits = self._prefix_cache.lookup(handle.request.prompt)
                    if hits:
                        self.slots.share(
                            slot, [p for entry in hits for p in entry]
                        )
                self.slots.reserve(
                    slot, self._total_need_tokens(handle.request)
                )
            except Exception as exc:
                # the popped handle is in neither the queue nor any slot
                # table yet, so _abort() cannot reach it — finish it HERE
                handle._finish(
                    FAILED, self.now(), error=f"admission failed: {exc!r}"
                )
                raise
            handle.prefix_hit_tokens = fill
            handle.admitted_at = self.now()
            self._h_queue_wait.observe(handle.admitted_at - handle.submitted_at)
            handle.status = RUNNING
            self._prefilling[slot] = _PrefillJob(handle, fill=fill)

    def _paged_admission_fits(self, handle: RequestHandle) -> bool:
        """True when the page pool can cover the request's reservation
        (after the prefix hit it is about to take). A shortfall first
        reclaims cold prefix-cache pages (a PAGE FAULT — counted), then
        gives up and lets the request wait."""
        need_total = self.slots.blocks_for(self._total_need_tokens(handle.request))
        # page reservation floors: pages held back for higher classes are
        # invisible to THIS class's admission (batch can never consume the
        # pool headroom gold admission needs)
        held_above = self._pages_reserved_above(handle.request.qos)
        for attempt in (0, 1):
            hit_blocks = 0
            if self._prefix_cache is not None:
                fill, _ = self._prefix_cache.walk(handle.request.prompt)
                hit_blocks = fill // self.page_size
            shortfall = (need_total - hit_blocks) - (
                self.slots.pool.available - held_above
            )
            if shortfall <= 0:
                return True
            if attempt or self._prefix_cache is None or not len(self._prefix_cache):
                return False
            # reclaim may evict the very entries the hit would have used —
            # the re-walk above recomputes the hit honestly on retry
            self.stats["page_faults"] += 1
            freed = self._prefix_cache.reclaim(shortfall)
            self.stats["pages_reclaimed"] += freed
        return False

    # ------------------------------------------------------- chunked prefill

    def _chunk_args(self, group, windows, starts, lens, index_after) -> tuple:
        """The chunk-prefill program's arguments for the slots ``group``
        (at most ``prefill_rows`` of them; ``windows`` maps a slot to its
        prompt window), padded to the program's rows. ``starts`` and
        ``lens`` are per SLOT; the program's are per row."""
        R, C, S = self.prefill_rows, self.prefill_chunk, self.n_slots
        # a padded entry: row id n_slots (no slot), zero tokens, start 0
        tokens = np.zeros((R, C), np.int32)
        rows = np.full((R,), S, np.int32)
        row_starts = np.zeros((R,), np.int32)
        row_lens = np.zeros((R,), np.int32)
        for i, slot in enumerate(group):
            window = windows[slot]
            tokens[i, : len(window)] = window
            rows[i], row_starts[i], row_lens[i] = slot, starts[slot], lens[slot]
        return (
            self.model,
            self.params,
            self.slots.cache,
            jnp.asarray(tokens),
            jnp.asarray(row_starts),
            jnp.asarray(row_lens),
            jnp.asarray(rows),
            jnp.asarray(self.slots.table),
            index_after,
        )

    # graftlint: hot-path
    # graftlint: supervised-seam
    def _prefill_tick(self) -> bool:
        """Process ONE chunk for every mid-prefill slot, ``prefill_rows``
        slots to a [rows, chunk] dispatch (one dispatch unless a burst of
        admissions prefills more slots than that at once), and install the
        slots whose prompt completed (their decode starts this same tick).

        Supervised, and THE FAULT RULE of a chunk dispatch (the other
        places point here). The chunk program donates the cache it is
        handed, as the decode step does, so what a fault costs is decided
        by what the engine can observe afterwards. BEFORE the hand-over
        (the chaos hook, building the arguments, the dispatch sanitizer, a
        trace or compile error: the engine's cache leaves are all alive):
        only the prefilling slots fail, decoding slots keep their buffers
        and the tick proceeds to a normal fused decode
        (``_on_prefill_fault``; no rebuild, no breaker). AFTER it (a leaf
        of the cache ``is_deleted()``: the faulted call consumed the
        pools): the fault is the tick's: ``_on_tick_fault`` fails every
        active and mid-prefill slot retryably, feeds the breaker and
        rebuilds the device state; ``prefill_faults_escalated`` counts how
        often the merged fault domain was paid for."""
        if not self._prefilling:
            return False
        self._prefill_work = True
        C, L, S = self.prefill_chunk, self.cache_len, self.n_slots
        windows: Dict[int, Sequence[int]] = {}  # slot -> its prompt window
        starts = [0] * S
        lens = [0] * S
        active = [False] * S
        faulted: List[int] = []
        for slot, job in self._prefilling.items():
            prompt = job.handle.request.prompt
            # clamp the window to capacity: the final chunk of a prompt
            # ending near the cap re-sends a few earlier tokens (their K/V
            # recompute bit-identically — the forward is deterministic)
            # instead of letting the device write clamp out of alignment.
            # (The re-sent overlap may rewrite SHARED pages — with
            # bit-identical values, by the same determinism argument, so no
            # copy-on-write is spent on it.)
            w = min(job.fill, L - C)
            # pages cover only REAL prompt positions: the window's padded
            # tail past len(prompt) routes to the trash page (unallocated
            # blocks map there), and ensuring w + C would draw pages beyond
            # the slot's admission reservation — stealing from already-
            # admitted neighbors and breaking the no-mid-flight-fault
            # invariant
            if not self._ensure_pages_or_reclaim(
                slot, min(w + C, len(prompt))
            ):
                faulted.append(slot)
                continue
            windows[slot] = prompt[w : w + C]
            starts[slot], lens[slot], active[slot] = w, len(prompt), True
        if faulted:
            # reservation-backed allocation makes this unreachable unless
            # bookkeeping rots; fail ONLY the starved jobs, loudly
            now = self.now()
            for slot in faulted:
                job = self._prefilling.pop(slot)
                self.stats["preemptions"] += 1
                job.handle._finish(
                    FAILED, now,
                    error="KV page pool exhausted during prefill (retryable)",
                    retryable=True,
                )
            self.slots.release(faulted)
            self._event("page_preemption", slots=len(faulted), phase="prefill")
            if not self._prefilling:
                return True
        # every dispatch of the tick writes the cursors the tick ENDS with:
        # nothing reads a cursor between them (a dispatch stages its own
        # rows' from ``starts``), and a fault releases every mid-prefill slot
        index_after = jnp.asarray(self._index_after(starts, lens, active))
        live = list(windows)
        R = self.prefill_rows
        try:
            for group in (live[i : i + R] for i in range(0, len(live), R)):
                self._prefill_dispatch(group, windows, starts, lens, index_after)
        except CompileFamilyExceeded:
            # strict-mode sanitizer trip: the whole point is the readable
            # signature listing — it must reach the test harness, not be
            # classified as a prefill fault and fed to the breaker
            raise
        except Exception as exc:
            if any(leaf.is_deleted() for leaf in jax.tree.leaves(self.slots.cache)):
                self.stats["prefill_faults"] += 1
                self.stats["prefill_faults_escalated"] += 1
                self._event("prefill_fault", error=repr(exc), escalated=True,
                            slots_failed=self.active_count + len(self._prefilling))
                # ring entry first, as for a decode tick's fault: a breaker
                # trip's dump must hold the tick that tripped it
                self.flight.tick({
                    "tick": self._tick, "fault": True, "error": repr(exc),
                    "queued": len(self._queue),
                })
                self._on_tick_fault(exc)
            else:
                self._on_prefill_fault(exc)
        return True

    # graftlint: hot-path
    def _prefill_dispatch(self, group, windows, starts, lens, index_after) -> None:
        """One dispatch of the chunk program for the slots ``group``: adopt
        the cache it returns, advance their jobs and install those whose
        prompt completed. A fault propagates to ``_prefill_tick``, whose
        rule asks whether the call had consumed the cache it was handed
        (donated) or the engine still holds the last dispatch's.

        ONE chunk program is in flight at a time: the wait bounds how far
        the host runs ahead of the device (a tick's second dispatch;
        prefill-only ticks, which wait for nothing: a long prompt arriving
        at an idle engine). It bounds no memory: the cache is donated, so
        a program in flight holds no pool-sized output. In a tick that
        decodes the program before has long run (the tick's device_get
        waited for it) and this costs nothing."""
        C = self.prefill_chunk
        before, self._chunk_in_flight = self._chunk_in_flight, None
        if before is not None:
            # the tick thread's one wait for the device beside device_wait
            with self.tracer.span("chunk_wait", "engine", tick=self._tick):
                # graftlint: allow[host-sync-in-hot-path] reason=waits only where the host would run ahead of the device (a burst's later dispatches, prefill-only ticks); bounds the run-ahead at one chunk program in flight
                jax.block_until_ready(before)
        # prefill_chunk covers an asynchronous dispatch: the chunk
        # program's device time is waited for in this tick's decode_step
        with self.tracer.span("prefill_chunk", "engine", tick=self._tick,
                              slots=len(group), rows=self.prefill_rows):
            if self._chaos is not None:
                self._chaos.on_prefill_chunk(self._tick)
            chunk_args = self._chunk_args(group, windows, starts, lens, index_after)
            # observe skips model+params (engine-lifetime constants):
            # the describe walk stays O(per-tick args), not O(params)
            self._ds_prefill.observe(*chunk_args[2:])
            cache, last, self._prefill_touched = _in_mesh(
                self.mesh, self._paged_chunk, *chunk_args
            )
        self.slots.cache = cache
        self.stats["prefill_chunks"] += len(group)
        self.stats["prefill_rows_live"] += len(group)
        self.stats["prefill_rows_computed"] += self.prefill_rows
        if self._has_state:
            self.stats["state_resets"] += sum(starts[s] == 0 for s in group)
        completed = []
        for slot in group:
            job = self._prefilling[slot]
            # ledger attribution: this request paid for one chunk row
            # of the batched dispatch (sums to stats["prefill_chunks"])
            job.handle.ledger["prefill_chunks"] += 1
            job.fill = min(starts[slot] + C, lens[slot])
            if job.fill >= lens[slot]:
                completed.append((slot, job))
        self._chunk_in_flight = last
        if completed:
            self._install_completed(completed, last)

    def _index_after(self, starts, lens, active) -> np.ndarray:
        """Every row's true post-chunk cursor, host-derived (the chunk
        program overwrites index leaves wholesale): mid-prefill rows
        advance their fill, decoding rows sit at prompt + emitted, parked
        rows at zero. An int32 host array: it reaches the device as a
        transfer, not a conversion launched there."""
        out = np.zeros((self.n_slots,), np.int32)
        C = self.prefill_chunk
        for slot in range(self.n_slots):
            if active[slot]:
                out[slot] = min(starts[slot] + C, lens[slot])
            elif self._active[slot] is not None:
                act = self._active[slot]
                out[slot] = len(act.handle.request.prompt) + act.emitted
        return out

    def _ensure_pages_or_reclaim(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s block table to cover ``tokens`` positions;
        on pool exhaustion reclaim cold prefix-cache pages (page fault)
        and retry once. Reservations make failure a bookkeeping bug, but
        the path stays defensive rather than trusting the proof."""
        tokens = min(tokens, self.cache_len)
        if self.slots.ensure(slot, tokens):
            return True
        self.stats["page_faults"] += 1
        if self._prefix_cache is not None and len(self._prefix_cache):
            need = self.slots.blocks_for(tokens) - self.slots.alloc_blocks[slot]
            freed = self._prefix_cache.reclaim(need)
            self.stats["pages_reclaimed"] += freed
            if self.slots.ensure(slot, tokens):
                return True
        return False

    # graftlint: hot-path
    def _handoff_completed(self, ship, last_rows) -> None:
        """Disaggregation SEND: a finished prefill whose request names a
        decode target ships its pages + first-token logits there instead of
        installing into this replica's decode set. The destination installs
        the exact carry a local install would have (logits row at
        true_len - 1, PRNGKey(seed), cleared mask/veto), so the handed-off
        stream is byte-identical to having decoded here — with zero
        recomputed tokens."""
        # graftlint: allow[host-sync-in-hot-path] reason=THE designed handoff sync — one device_get of the shipping rows' logits, only on prefill-role completions
        rows = jax.device_get(last_rows)
        now = self.now()
        for slot, job in ship:
            handle = job.handle
            handle.prefill_done_at = now
            if handle.admitted_at is not None:
                self._h_prefill.observe(now - handle.admitted_at)
            # bank the prefix BEFORE detaching: the banked pages' refcounts
            # survive the slot release, so the prefill replica's chunk
            # cache actually accumulates — the whole point of the router's
            # prefill affinity on a disaggregated fleet
            self._bank_prefix(slot, handle)
            try:
                span = self.slots.export_page_span(
                    slot, len(handle.request.prompt)
                )
            except Exception as exc:  # a bad export fails ONLY this stream, retryably
                self._detach_slot(slot, True)
                self._migration_failed(handle, f"export failed: {exc!r}")
                continue
            leaves = dict(span["leaves"])
            leaves["carry/last_logits"] = np.asarray(
                rows[slot], np.float32
            )
            leaves["carry/gen_mask"] = np.zeros(
                (self.cfg.vocab_size,), np.bool_
            )
            leaves["carry/rng"] = _host_key(handle.request.seed)
            payload = {
                **self._stream_meta(
                    handle, list(handle.request.prompt),
                    handle.request.max_new_tokens,
                ),
                "kind": "decode",
                "veto": -1,
                "page_size": span["page_size"],
                "n_blocks": span["n_blocks"],
                "n_tokens": span["n_tokens"],
                "leaves": leaves,
            }
            self._detach_slot(slot, True)
            with self._lock:
                self._migrating[handle.id] = handle
                self._migrations_in_flight += 1
            self._ship(payload, handle.request.prefill_to, handle)

    def _install_completed(self, completed, last_rows) -> None:
        """Move slots whose prefill just finished into the decode set (one
        coalesced install), then bank their chunk-aligned prefix spans so
        the NEXT prompt sharing the prefix skips them. Completions whose
        request names a decode target (``prefill_to``) ship instead.
        All of it is the ``install`` span: the admission's host work and
        its small launches, after the chunk program's dispatch."""
        with self.tracer.span("install", "engine", tick=self._tick,
                              slots=len(completed)) as install_span:
            ship = [
                (s, j) for s, j in completed
                if j.handle.request.prefill_to is not None
            ]
            if ship:
                install_span.note(shipped=len(ship))
                self._handoff_completed(ship, last_rows)
                completed = [
                    (s, j) for s, j in completed
                    if j.handle.request.prefill_to is None
                ]
                if not completed:
                    return
            mask = np.zeros((self.n_slots,), np.bool_)
            keys = np.zeros(self._rngs.shape, np.uint32)
            for slot, job in completed:
                mask[slot] = True
                keys[slot] = _host_key(job.handle.request.seed)
            (self._last_logits, self._gen_mask, self._rngs,
             self._veto) = _in_mesh(
                self.mesh,
                _install_rows,
                self._last_logits,
                self._gen_mask,
                self._rngs,
                self._veto,
                mask,
                last_rows,
                keys,
            )
            t_done = self.now()
            for slot, job in completed:
                del self._prefilling[slot]
                job.handle.prefill_done_at = t_done
                if job.handle.admitted_at is not None:
                    self._h_prefill.observe(t_done - job.handle.admitted_at)
                self._active[slot] = _ActiveSlot(job.handle)
                self.stats["peak_occupancy"] = max(
                    self.stats["peak_occupancy"], self.active_count
                )
                self._bank_prefix(slot, job.handle)

    def _bank_prefix(self, slot: int, handle: RequestHandle) -> None:
        """Bank a completed prefill's chunk-aligned prefix pages so the
        NEXT prompt sharing the prefix skips them. Store BEFORE the first
        decode write (and before a handoff detaches the slot): positions
        [0, T) are all real prompt K/V right now. Banking is PURE
        BOOKKEEPING — the slot's pages get one more reference and their
        ids land in the index; no bytes move (the reference survives the
        slot's release, which is what lets prefill-role replicas keep a
        live chunk cache). Skipped entirely when the cache already holds
        the full prefix."""
        if self._prefix_cache is None:
            return
        prompt = handle.request.prompt
        C = self.prefill_chunk
        n_chunks = len(prompt) // C
        if n_chunks and not all(
            self._prefix_cache.contains(prompt, j)
            for j in range(1, n_chunks + 1)
        ):
            bpc = C // self.page_size  # blocks per chunk
            pages = self.slots.bank(slot, n_chunks * bpc)
            for j in range(1, n_chunks + 1):
                self._prefix_cache.store_pages(
                    prompt, j, pages[(j - 1) * bpc : j * bpc]
                )

    def _on_prefill_fault(self, exc: Exception) -> None:
        """A chunk-prefill dispatch failed BEFORE it was handed the cache
        (``_prefill_tick``'s rule: every cache leaf is alive): fail ONLY
        the slots mid-prefill (retryable error to those clients) and keep
        everything else: the buffers the engine holds (including every
        decoding slot's rows) are intact and nothing needs a rebuild.
        Unlike decode faults this does not feed the breaker: blast radius
        is per-request and bounded, and the shared decode executable was
        never implicated."""
        self.stats["prefill_faults"] += 1
        now = self.now()
        failed = sorted(self._prefilling)
        for slot in failed:
            job = self._prefilling.pop(slot)
            job.handle._finish(
                FAILED,
                now,
                error=f"prefill chunk failed (retryable): {exc!r}",
                retryable=True,
            )
        self.slots.release(failed)
        self._event("prefill_fault", error=repr(exc), slots_failed=len(failed))

    def _retire(self, finished: List[int]) -> None:
        self.slots.release(finished)
        for slot in finished:
            self._active[slot] = None

    def _sweep_active(self) -> None:
        """Drop cancelled / past-deadline slots BEFORE the tick so their
        token is neither computed against a dead deadline nor emitted."""
        now = self.now()
        finished = []
        for slot, act in enumerate(self._active):
            if act is None:
                continue
            if act.handle._cancel.is_set():
                self.stats["cancelled"] += 1
                act.handle._finish(CANCELLED, now)
                finished.append(slot)
            elif (
                act.handle.request.deadline is not None
                and now > act.handle.request.deadline
            ):
                self.stats["expired_decoding"] += 1
                act.handle._finish(EXPIRED, now, error="deadline expired mid-decode")
                finished.append(slot)
        self._retire(finished)
        # mid-prefill slots honor cancel/deadline at the same tick boundary
        dropped = []
        for slot, job in self._prefilling.items():
            if job.handle._cancel.is_set():
                self.stats["cancelled"] += 1
                job.handle._finish(CANCELLED, now)
            elif (
                job.handle.request.deadline is not None
                and now > job.handle.request.deadline
            ):
                # its own counter, not expired_decoding: an operator tuning
                # against prefill-phase expiries (prompt length vs chunk
                # budget) must not be steered at decode budgets
                self.stats["expired_prefilling"] += 1
                job.handle._finish(
                    EXPIRED, now, error="deadline expired during prefill"
                )
            else:
                continue
            dropped.append(slot)
        for slot in dropped:
            del self._prefilling[slot]
        self.slots.release(dropped)

    def _sweep_queue(self) -> None:
        """Finish cancelled / past-deadline requests still WAITING, every
        tick — not only when a free slot lets ``_admit`` pop them. With all
        slots busy on long generations, a queued request's deadline (and
        ``cancel()``'s next-tick promise) must not wait for a slot to free."""
        now = self.now()
        with self._lock:
            kept: List[RequestHandle] = []
            dropped = False
            for cand in self._queue:
                if cand._cancel.is_set():
                    self.stats["cancelled"] += 1
                    cand._finish(CANCELLED, now)
                    dropped = True
                elif cand.request.deadline is not None and now > cand.request.deadline:
                    self.stats["expired_queued"] += 1
                    cand._finish(EXPIRED, now, error="deadline expired in queue")
                    dropped = True
                else:
                    kept.append(cand)
            if dropped:
                self._queue.rebuild(kept)

    # graftlint: hot-path
    # graftlint: supervised-seam
    def step(self) -> bool:
        """One scheduler tick: swap-in reload, sweep, admit, chunk-prefill
        budget (one chunk per mid-prefill slot, batched), supervised fused
        decode, emit, retire. Returns False when there was nothing to do."""
        # staged profile windows start/advance/stop here — the tick thread
        # owns the process-global jax profiler. Keyed on the BUSY-tick
        # counter (self._tick), so "capture N ticks" means N ticks of real
        # work, not N idle spins of the scheduler loop
        self._profiler.poll(self._tick)
        # the tick's span tree (live spans: the ring on the engine's clock,
        # "engine/<name>" annotations on the profiler's while a capture is
        # open):  tick > schedule, prefill > chunk_wait + prefill_chunk +
        # install, grow_pages, decode_step > dispatch + device_wait, emit.
        # What the children do not cover is the tick's self time.
        with self.tracer.span("tick", "engine", tick=self._tick) as tick_span:
            return self._run_tick(tick_span)

    # graftlint: hot-path
    # graftlint: supervised-seam
    def _run_tick(self, tick_span) -> bool:
        tr = self.tracer
        tick_idx = self._tick
        with tr.span("schedule", "engine", tick=tick_idx) as sched_span:
            self._swap_pending_params()
            self._sweep_queue()
            self._sweep_active()
            self._service_migrations()
            self._service_imports()
            self._prefill_work = False
            self._admit()
            if not (self._prefilling or self.active_count or self._breaker.open):
                # nothing admitted, nothing running: this spin will record
                # no tick, and a parked engine spins a thousand times a
                # second — keep its schedule out of the ring too
                sched_span.discard()
        ran_prefill = False
        if self._prefilling:
            rows_before = self.stats["prefill_rows_computed"]
            with tr.span("prefill", "engine", tick=tick_idx):
                ran_prefill = self._prefill_tick()
            # the chunk program's dispatches this tick, from the counter each
            # one already keeps: a chunk tick is told by its ``tick`` record
            chunks = (self.stats["prefill_rows_computed"] - rows_before) // self.prefill_rows
            if chunks:
                tick_span.note(chunks=chunks)
        if self.active_count:
            with tr.span("grow_pages", "engine", tick=tick_idx):
                self._grow_decode_pages()
        # an idle DEGRADED engine still runs the fused step as a self-probe
        # (all rows parked, outputs discarded): without it, a load balancer
        # honoring the 503 starves the engine of the clean tick it needs to
        # close the breaker, and the replica would stay DEGRADED forever
        probe = self._breaker.open and self.active_count == 0
        if self.active_count == 0 and not probe:
            if ran_prefill:
                # prefill-only tick: nothing decodes yet, but the tick did
                # real work and the loop must not sleep
                tick_span.note(phase="prefill_only")
                self.flight.tick({
                    "tick": tick_idx, "prefilling": len(self._prefilling),
                    "active": 0, "queued": len(self._queue), "emitted": 0,
                })
                self._tick += 1
                return True
            # a spin that found nothing to do is no tick of work: the ring
            # stays unwritten (serve_mfu sums every ``tick`` as work)
            tick_span.discard()
            return False

        # -- supervised region: a fault here poisons AT MOST this tick's
        # active slots, never the scheduler thread (run() stays alive and
        # queued requests admit on the next tick)
        try:
            self.stats["loop_passes"] += self.cfg.n_loops
            table_pages = self.n_slots * self.slots.n_blocks
            self.stats["kernel_pages_table"] += table_pages
            self.stats["kernel_pages_live"] += sum(
                max(1, n) for n in self.slots.alloc_blocks
            )
            # decode_step is dispatch plus the host's wait for everything
            # the device still owes this tick: the decode program AND the
            # prefill program that prefill_chunk only dispatched. It is
            # host time; the programs' own durations are in a capture's
            # "XLA Modules" line.
            with tr.span("decode_step", "engine", tick=tick_idx,
                         active=self.active_count, spec=bool(self.draft_k),
                         loops=self.cfg.n_loops,
                         pages_in_use=self.slots.pool.in_use,
                         table_pages=table_pages) as step_span:
                if self._chaos is not None:
                    self._chaos.on_tick(self._tick)
                # one batched push of every block-table change this tick
                # (admissions, growth, retirements) before the fused step
                # reads the device tables
                self.slots.flush_tables()
                if self.draft_k and self._spec_enabled:
                    blocks, n_emits, bad_rows = self._dispatch_spec(tick_idx)
                else:
                    with tr.span("dispatch", "engine", tick=tick_idx):
                        fused_args = (
                            self.model,
                            self.sampling,
                            self.params,
                            self._last_logits,
                            self.slots.cache,
                            self._gen_mask,
                            self._rngs,
                        )
                        if self._sends_decoding:
                            # the rows that decode, on the device; sent
                            # again only when a slot joins or leaves, as a
                            # host array (a transfer, no launch)
                            live = list(act is not None for act in self._active)
                            if live != self._decoding[0]:
                                self._decoding = (
                                    live, jnp.asarray(np.array(live, np.bool_))
                                )
                            fused_args += (self._decoding[1],)
                        if self._has_state:
                            # rows whose state this tick advances, the
                            # bytes the state update moves (those rows'
                            # state in and out: it visits no other row),
                            # the slots it leaves in HBM untouched, and the
                            # slots that hold one (the gauge's count)
                            skipped = self.n_slots - self.active_count
                            self.stats["state_rows_skipped"] += skipped
                            step_span.note(
                                state_rows=self.active_count,
                                state_bytes=2 * self.active_count
                                * self._state_bytes_per_slot,
                                state_rows_skipped=skipped,
                                state_rows_in_use=self.state_rows_in_use,
                            )
                        # skip model (0) + params (2) — engine-lifetime
                        # constants; sampling statics + cache/logits/mask/rng
                        # shapes remain
                        self._ds_decode.observe(fused_args[1], *fused_args[3:])
                        if self._paged_kernel:
                            # the paged kernel's compiled family is selected by
                            # the table/pool shapes inside the cache tree plus
                            # the decode window — pin them at bound 1
                            self._ds_paged.observe(
                                fused_args[4], 1 + self.draft_k
                            )
                        token, self._last_logits, self.slots.cache, self._gen_mask, self._rngs, bad, routing = _in_mesh(
                            self.mesh, self._fused, *fused_args
                        )
                        if self._chaos is not None:
                            # injected NaNs land AFTER the step, so re-run the same
                            # predicate over the poisoned logits — injected and organic
                            # NaNs are judged by the identical criterion (the extra
                            # dispatch is chaos-only; the healthy path stays at one)
                            self._last_logits = self._chaos.poison_logits(
                                self._tick, self._last_logits
                            )
                            bad = _in_mesh(self.mesh, nonfinite_rows, self._last_logits)
                    with tr.span("device_wait", "engine", tick=tick_idx):
                        # graftlint: allow[host-sync-in-hot-path] reason=THE designed per-tick sync — one coalesced device_get of token + poison mask (PR 2's one-sync budget); every other read rides it
                        tokens, bad_rows, routing, prefill_touched = jax.device_get(
                            (token, bad, routing, self._prefill_touched)
                        )
                    if prefill_touched is not None:
                        self._prefill_touched = None
                        step_span.note(prefill_experts_touched=int(prefill_touched))
                    if routing is not None:
                        routed, load_max, touched = (int(n) for n in routing)
                        self.stats["moe_tokens_routed"] += routed
                        self.stats["moe_expert_load_max"] += load_max
                        self.stats["moe_expert_load_mean"] += routed / self.cfg.n_experts
                        step_span.note(experts_touched=touched, moe_routed=routed,
                                       moe_load_max=load_max)
                    blocks = [[int(t)] for t in tokens.tolist()]
                    n_emits = [1] * self.n_slots
        except CompileFamilyExceeded:
            # strict-mode sanitizer trip: surface the signature listing to
            # the test harness instead of feeding it to the breaker as an
            # opaque tick fault (non-strict mode never raises — it warns)
            raise
        except Exception as exc:
            # ring entry FIRST: a breaker trip inside _on_tick_fault dumps
            # the recorder, and the dump must contain the tick that tripped
            tick_span.note(fault=True)
            self.flight.tick({
                "tick": tick_idx, "fault": True, "error": repr(exc),
                "queued": len(self._queue),
            })
            self._on_tick_fault(exc)
            self._tick += 1
            return True
        if self._breaker.record_clean():
            self._rebuilds_since_recovery = 0
            if not self.draining:
                self.lifecycle.to(READY, reason="breaker closed after clean tick")
            self._event("breaker_closed")

        with tr.span("emit", "engine", tick=tick_idx) as emit_span:
            now = self.now()
            finished: List[int] = []
            poisoned: List[int] = []
            ttft_new: List[tuple] = []  # (sample_s, qos_class)
            itl_new: List[tuple] = []
            tokens_before = self.stats["tokens_out"]
            for slot, act in enumerate(self._active):
                if act is None:
                    continue
                qos_cls = self.qos.normalize(act.handle.request.qos)
                toks = blocks[slot][: n_emits[slot]]
                # cost ledger: one decode tick held, at this slot's current KV
                # page footprint (pages x ticks is the capacity-time integral a
                # tenant actually consumed)
                act.handle.ledger["decode_ticks"] += 1
                act.handle.ledger["pages_held_ticks"] += (
                    self.slots.alloc_blocks[slot]
                )
                if act.emitted == 0:
                    ttft_new.append((now - act.handle.submitted_at, qos_cls))
                elif act.last_emit_at is not None:
                    # a speculative tick delivers its accepted block in one
                    # burst; one AMORTIZED sample per token keeps the ITL
                    # percentiles honest about per-token latency (n_emit = 1
                    # degenerates to the classic one-sample-per-tick)
                    gap = now - act.last_emit_at
                    itl_new.extend([(gap / len(toks), qos_cls)] * len(toks))
                # the block's first token was sampled from the PREVIOUS (finite)
                # logits, so it is valid even when the new logits went bad —
                # emit it, then retire the poisoned slot with a retryable error
                # (a bad row's n_emit is already clamped to that first token:
                # drafts "verified" by garbage logits are never emitted)
                done_now = False
                for t in toks:
                    act.handle._emit(int(t), now)
                    act.emitted += 1
                    act.last_emit_at = now
                    self.stats["tokens_out"] += 1
                    act.handle.ledger["tokens_out"] += 1
                    hit_eos = (
                        self.eos_token_id is not None and int(t) == self.eos_token_id
                    )
                    if hit_eos or act.emitted >= act.handle.request.max_new_tokens:
                        # completion outranks the poison flag: the tokens
                        # emitted so far all trace to finite logits, so a
                        # request finishing now delivered a fully valid output
                        act.handle._finish(DONE, now)
                        self.stats["completed"] += 1
                        finished.append(slot)
                        done_now = True
                        break
                if not done_now and bool(bad_rows[slot]):
                    act.handle._finish(
                        FAILED, now,
                        error="non-finite logits in decode (retryable)",
                        retryable=True,
                    )
                    self.stats["poisoned_slots"] += 1
                    poisoned.append(slot)
                    finished.append(slot)
                elif not done_now and act.handle.overflowed:
                    # the STREAMING consumer stopped draining past the emit
                    # buffer bound: stop paying slot/page capacity for a
                    # reader that went away. Retryable — the done event always
                    # delivers, so a recovered client re-submits cleanly.
                    act.handle._finish(
                        FAILED, now,
                        error=(
                            "client stalled mid-stream; emit buffer "
                            "overflowed (retryable)"
                        ),
                        retryable=True,
                    )
                    self.stats["stalled_streams"] += 1
                    finished.append(slot)
                    self._event("stalled_stream", request_id=act.handle.rid)
            if any(bad_rows):
                # zero EVERY bad row (poisoned-and-retired or finished-anyway)
                # so a parked slot never feeds NaN back into the next tick's
                # sample — retirement alone leaves the row in place
                keep = jnp.asarray([not b for b in bad_rows], jnp.bool_)
                self._last_logits = jnp.where(keep[:, None], self._last_logits, 0.0)
            if poisoned:
                self._event("poisoned_slots", slots=len(poisoned))
            # histograms carry their own micro-locks — no scheduler lock, and a
            # concurrent /metrics scrape reads bucket counts, never a sample list
            for sample, cls in ttft_new:
                self._h_ttft.observe(sample)
                self._h_ttft_class[cls].observe(sample)
            for sample, cls in itl_new:
                self._h_itl.observe(sample)
                self._h_itl_class[cls].observe(sample)
                if not self._prefill_work:
                    # per-phase attribution: this tick ran no prefill
                    # chunk, so these samples are the pure-decode ITL floor
                    self._h_itl_decode.observe(sample)
                self._itl_ewma.update(sample)
            self._retire(finished)
            emit_span.note(finished=len(finished))
            # the tick's own record keeping closes the phase, so that what
            # ``tick`` holds beyond its children is span overhead alone
            self.flight.tick({
                "tick": tick_idx, "active": self.active_count,
                "prefilling": len(self._prefilling), "queued": len(self._queue),
                "emitted": self.stats["tokens_out"] - tokens_before,
                "finished": len(finished), "poisoned": len(poisoned),
            })
            self._tick += 1
            if (
                self.metrics is not None
                and self.metrics_interval
                and self._tick % self.metrics_interval == 0
            ):
                self.metrics.log(self.metrics_snapshot(), step=self._tick, prefix="serve")
        return not probe

    # --------------------------------------------------- speculative decode

    # graftlint: hot-path
    def _dispatch_spec(self, tick_idx: int):
        """Run the speculative fused step for this tick: host-propose K
        draft tokens per decoding slot (prompt-lookup over the slot's own
        prompt + emitted history, or the engine's pluggable ``draft_fn``),
        verify them all in ONE batched forward, and return per-slot emit
        blocks. A row whose verify logits went non-finite is clamped to its
        first token (sampled from the previous, finite distribution) — the
        plain step's exact poison semantics."""
        tr = self.tracer
        with tr.span("dispatch", "engine", tick=tick_idx):
            K, S = self.draft_k, self.n_slots
            V = self.cfg.vocab_size
            drafts = [[0] * K for _ in range(S)]
            active = [a is not None for a in self._active]
            for slot, act in enumerate(self._active):
                if act is None:
                    continue
                hist = list(act.handle.request.prompt) + act.handle.tokens
                d = [int(t) for t in self.draft_fn(hist, K)]
                # clamp a misbehaving custom draft_fn: wrong-length or
                # out-of-vocab drafts must degrade acceptance, not crash a tick
                drafts[slot] = [t % V for t in d[:K]] + [0] * (K - len(d))
            spec_args = (
                self.model,
                self.sampling,
                K,
                self.params,
                self._last_logits,
                self.slots.cache,
                self._gen_mask,
                self._rngs,
                jnp.asarray(drafts, jnp.int32),
                self._veto,
                jnp.asarray(active, jnp.bool_),
            )
            # skip model (0) + params (3) — engine-lifetime constants
            self._ds_spec.observe(*spec_args[1:3], *spec_args[4:])
            if self._paged_kernel:
                self._ds_paged.observe(spec_args[5], 1 + K)
            x, n_acc, self._last_logits, self.slots.cache, self._gen_mask, self._rngs, self._veto, bad = _in_mesh(
                self.mesh, self._spec, *spec_args
            )
            if self._chaos is not None:
                self._last_logits = self._chaos.poison_logits(
                    self._tick, self._last_logits
                )
                bad = bad | _in_mesh(self.mesh, nonfinite_rows, self._last_logits)
        with tr.span("device_wait", "engine", tick=tick_idx):
            # graftlint: allow[host-sync-in-hot-path] reason=THE designed per-tick sync of the speculative path — one coalesced device_get of the accepted block + counts + poison mask
            xs, n_accs, bad_rows = jax.device_get((x, n_acc, bad))
        self.stats["spec_ticks"] += 1
        blocks = [row.tolist() for row in xs]
        n_emits = [1] * S
        for slot in range(S):
            if not active[slot]:
                continue
            self.stats["draft_tokens"] += K
            ledger = self._active[slot].handle.ledger
            ledger["draft_tokens"] += K
            if not bool(bad_rows[slot]):
                acc = int(n_accs[slot])
                self.stats["accepted_tokens"] += acc
                ledger["accepted_tokens"] += acc
                n_emits[slot] = 1 + acc
        return blocks, n_emits, bad_rows

    # ------------------------------------- transferable streams (migration)

    @property
    def migrations_in_flight(self) -> int:
        """Streams exported and awaiting the ship acknowledgement."""
        return self._migrations_in_flight

    def request_migration(self, request_id: str, target: str) -> bool:
        """Ask the tick thread to migrate the live stream ``request_id`` to
        ``target`` (a replica base URL). Thread-safe; returns False when no
        live stream carries that id (the caller maps it to 404). The export
        itself happens between ticks — device state stays tick-thread-owned."""
        # snapshot under the GIL (list() of a dict/list is one C-level op)
        # — the tick thread mutates both containers concurrently, and bare
        # iteration from this HTTP thread could see "changed size"
        if self._refuse_page_span("request_migration"):
            return False
        active = list(self._active)
        prefilling = list(self._prefilling.values())
        found = any(
            a is not None and a.handle.rid == request_id for a in active
        ) or any(j.handle.rid == request_id for j in prefilling)
        if not found:
            return False
        with self._lock:
            self._migrate_requests[request_id] = target
        return True

    def request_migrate_all(self, target: str) -> int:
        """Migrate EVERY live stream to ``target`` (scale-down / drain
        upgrade). Returns how many streams were tagged."""
        if self._refuse_page_span("request_migrate_all"):
            return 0
        n = sum(1 for a in list(self._active) if a is not None) + len(
            self._prefilling
        )
        if n:
            with self._lock:
                self._migrate_requests["*"] = target
        return n

    @property
    def state_rows_in_use(self) -> int:
        """Slots whose recurrent state a request holds: decoding or
        mid-prefill (0 for a model that keeps none)."""
        return self.active_count + len(self._prefilling) if self._has_state else 0

    def _refuse_page_span(self, what: str) -> bool:
        """A model with recurrent state exports and imports no stream: the
        wire format carries pages, and a span without its state is half a
        request. Counted and said; the caller reports 'not found' /
        rejects, and the router falls back to re-dispatch-and-recompute."""
        if not self._has_state:
            return False
        self.stats["state_refusals_page_span"] += 1
        self._event("page_span_refused", what=what, reason="recurrent state")
        return True

    # graftlint: hot-path
    def _service_migrations(self) -> None:
        """Tick-thread side of migration SEND: export each tagged slot's
        pages + decode carry, release the slot, and hand the payload to the
        shipper. The handle stays unfinished (status ``running``) until the
        ship acknowledges — success finishes it ``migrated`` (the router
        attaches at the target, zero tokens replayed), failure finishes it
        retryably (the router falls back to re-dispatch-and-recompute)."""
        with self._lock:
            reqs, self._migrate_requests = self._migrate_requests, {}
        if not reqs:
            return
        every = reqs.pop("*", None)
        jobs: List[tuple] = []  # (slot, handle, is_prefill, target)
        for slot, act in enumerate(self._active):
            if act is None:
                continue
            target = reqs.get(act.handle.rid, every)
            if target:
                jobs.append((slot, act.handle, False, target))
        for slot, job in list(self._prefilling.items()):
            target = reqs.get(job.handle.rid, every)
            if target:
                jobs.append((slot, job.handle, True, target))
        for slot, handle, is_prefill, target in jobs:
            try:
                if is_prefill:
                    payload = self._export_prefill(slot)
                else:
                    payload = self._export_decoding(slot)
            except Exception as exc:  # a bad export fails ONLY this stream, retryably
                self._detach_slot(slot, is_prefill)
                self._migration_failed(handle, f"export failed: {exc!r}")
                continue
            self._detach_slot(slot, is_prefill)
            with self._lock:
                self._migrating[handle.id] = handle
                self._migrations_in_flight += 1
            self._ship(payload, target, handle)

    def _detach_slot(self, slot: int, is_prefill: bool) -> None:
        """Free the slot WITHOUT finishing its handle (the handle's fate is
        the ship's to decide)."""
        if is_prefill:
            self._prefilling.pop(slot, None)
        else:
            self._active[slot] = None
        self.slots.release([slot])

    def _stream_meta(self, handle: RequestHandle, consumed: List[int],
                     remaining: int) -> Dict[str, Any]:
        req = handle.request
        deadline_s = (
            max(0.05, req.deadline - self.now())
            if req.deadline is not None else None
        )
        return {
            "request_id": handle.rid,
            "prompt": [int(t) for t in consumed],
            "max_new_tokens": int(remaining),
            "seed": int(req.seed),
            "deadline_s": deadline_s,
            "draft_k": self.draft_k,
            # cost-ledger carry: counters + the ms already spent here (the
            # handle is still LIVE, so wall time accrues to now), so the
            # destination's terminal event reports the CUMULATIVE cost of
            # the whole stream, not just its final hop
            "ledger": handle.ledger_snapshot(now=self.now()),
            "hop": handle.trace_hop,
        }

    # graftlint: hot-path
    def _export_decoding(self, slot: int) -> Dict[str, Any]:
        """Payload for a mid-decode stream: pages covering every consumed
        position [0, prompt + emitted) plus the decode carry (last_logits /
        gen_mask / rng / veto rows) — the destination continues the exact
        trajectory with zero recompute."""
        act = self._active[slot]
        handle = act.handle
        consumed = list(handle.request.prompt) + [int(t) for t in handle.tokens]
        cursor = len(consumed)
        span = self.slots.export_page_span(slot, cursor)
        # graftlint: allow[host-sync-in-hot-path] reason=THE designed migration-send sync — one coalesced device_get of the slot's decode carry, only when a stream migrates
        row, mask_row, key, veto = jax.device_get((
            self._last_logits[slot], self._gen_mask[slot],
            self._rngs[slot], self._veto[slot],
        ))
        meta = self._stream_meta(
            handle, consumed,
            handle.request.max_new_tokens - len(handle.tokens),
        )
        leaves = dict(span["leaves"])
        leaves["carry/last_logits"] = row
        leaves["carry/gen_mask"] = mask_row
        leaves["carry/rng"] = key
        return {
            **meta,
            "kind": "decode",
            "veto": int(veto),
            "page_size": span["page_size"],
            "n_blocks": span["n_blocks"],
            "n_tokens": span["n_tokens"],
            "leaves": leaves,
        }

    def _export_prefill(self, slot: int) -> Dict[str, Any]:
        """Payload for a mid-prefill stream: pages covering [0, fill) and
        the fill cursor — the destination finishes the remaining chunks
        (deterministic forward: bit-identical to never having moved)."""
        job = self._prefilling[slot]
        span = self.slots.export_page_span(slot, job.fill)
        meta = self._stream_meta(
            job.handle, list(job.handle.request.prompt),
            job.handle.request.max_new_tokens,
        )
        return {
            **meta,
            "kind": "prefill",
            "fill": int(job.fill),
            "page_size": span["page_size"],
            "n_blocks": span["n_blocks"],
            "n_tokens": span["n_tokens"],
            "leaves": dict(span["leaves"]),
        }

    def _ship(self, payload: Dict[str, Any], target: str,
              handle: RequestHandle) -> None:
        shipper = self.page_shipper
        if shipper is None:
            self._migration_failed(handle, "no page shipper configured")
            return

        def on_done(err: Optional[str]) -> None:
            if err is None:
                self._migration_done(handle, target)
            else:
                self._migration_failed(handle, err)

        try:
            shipper(payload, target, on_done)
        except Exception as exc:  # a shipper crash degrades to the recompute fallback
            self._migration_failed(handle, f"shipper raised: {exc!r}")

    def _migration_done(self, handle: RequestHandle, target: str) -> None:
        # runs on the SHIPPER's thread: every read-modify-write here races
        # the tick thread's increments, so all bookkeeping sits under the
        # engine lock (the gauge feeds the router's placement — drift
        # would be permanent)
        with self._lock:
            self._migrating.pop(handle.id, None)
            self._migrations_in_flight = max(0, self._migrations_in_flight - 1)
            if handle.status in _FINISHED:
                return  # an abort beat the ship ack; the client already heard
            handle.migrated_to = target
            self.stats["migrations_out"] += 1
            if handle.request.prefill_to is not None:
                self.stats["prefill_handoffs"] += 1
        handle._finish(MIGRATED, self.now())
        self._event(
            "stream_migrated", target=target, request_id=handle.rid,
            tokens_done=len(handle.tokens),
        )

    def _migration_failed(self, handle: RequestHandle, err: str) -> None:
        with self._lock:
            self._migrating.pop(handle.id, None)
            self._migrations_in_flight = max(
                0, self._migrations_in_flight - 1
            )
            finished = handle.status in _FINISHED
            if not finished:
                self.stats["migration_failures"] += 1
        if finished:
            return  # an abort beat the ship ack
        self._event("migration_failed", error=err, request_id=handle.rid)
        # post-mortem window: a failed ship is exactly when an operator
        # asks "what was the fleet doing" — dump while the ring still
        # holds the ticks around the export
        self.flight.dump(
            "migration_failed",
            extra={"error": err, "request_id": handle.rid},
        )
        handle._finish(
            FAILED, self.now(),
            error=f"migration failed: {err} (retryable)", retryable=True,
        )

    # ---- receive side ----------------------------------------------------

    @staticmethod
    def _validate_import_payload(payload) -> Optional[str]:
        """Structural check of a migrated-stream payload — everything the
        tick thread will later subscript must exist and parse, so a bad
        peer costs one rejected import, not the scheduler thread."""
        if not isinstance(payload, dict):
            return "payload must be a dict"
        for key in ("kind", "prompt", "max_new_tokens", "page_size",
                    "n_blocks", "leaves"):
            if key not in payload:
                return f"missing field {key!r}"
        if payload["kind"] not in ("decode", "prefill"):
            return f"unknown kind {payload['kind']!r}"
        if not isinstance(payload["leaves"], dict):
            return "leaves must be a dict"
        try:
            int(payload["max_new_tokens"])
            int(payload["page_size"])
            int(payload["n_blocks"])
            int(payload.get("veto", -1))
            [int(t) for t in payload["prompt"]]
            if payload.get("deadline_s") is not None:
                float(payload["deadline_s"])
            if payload["kind"] == "prefill":
                int(payload["fill"])
        except (TypeError, ValueError, KeyError) as exc:
            return f"unparseable field: {exc!r}"
        if payload["kind"] == "decode":
            for leaf in ("carry/last_logits", "carry/gen_mask", "carry/rng"):
                if leaf not in payload["leaves"]:
                    return f"missing decode carry leaf {leaf!r}"
        return None

    def import_stream(self, payload: Dict[str, Any]) -> RequestHandle:
        """Accept a migrated stream (any thread): validate, then queue it
        for the tick thread to place — device state stays tick-owned. The
        returned handle streams the CONTINUATION (only new tokens; the
        client already holds the rest). A handle that could not be accepted
        comes back already finished (rejected/failed, retryable where the
        condition is transient)."""
        now = self.now()
        # structural validation FIRST: a version-skewed or malformed peer
        # payload must become a clean retryable rejection here, never a
        # KeyError on the tick thread (which would abort the whole engine)
        structural = self._validate_import_payload(payload)
        if self._refuse_page_span("import_stream"):
            structural = "this model keeps recurrent state: page spans are refused"
        if structural is not None:
            handle = RequestHandle(
                Request([0], 1), next(self._ids), now,
                request_id=payload.get("request_id")
                if isinstance(payload, dict) else None,
            )
            handle._tracer = self.tracer
            handle._finish(
                REJECTED, now, error=f"bad import payload: {structural}",
                retryable=True,
            )
            return handle
        deadline = (
            now + float(payload["deadline_s"])
            if payload.get("deadline_s") is not None else None
        )
        request = Request(
            [int(t) for t in payload["prompt"]],
            int(payload["max_new_tokens"]),
            int(payload.get("seed", 0)),
            deadline,
        )
        handle = RequestHandle(
            request, next(self._ids), now,
            request_id=payload.get("request_id"),
        )
        handle._tracer = self.tracer
        self._seed_imported_ledger(handle, payload)
        if self.role == "prefill":
            handle._finish(
                REJECTED, now,
                error="prefill-role replica cannot import streams",
            )
            return handle
        if int(payload.get("draft_k", 0)) != self.draft_k:
            # the veto/rewind carry is draft_k-shaped; a mismatched fleet
            # config must degrade to the recompute fallback, not corrupt
            handle._finish(
                REJECTED, now,
                error=(
                    f"draft_k mismatch: stream {payload.get('draft_k')}, "
                    f"replica {self.draft_k}"
                ),
                retryable=True,
            )
            return handle
        invalid = self._validate(request)
        if invalid is not None:
            handle._finish(REJECTED, now, error=invalid)
            return handle
        with self._lock:
            if self._dead is not None:
                handle._finish(FAILED, now, error=self._dead)
                return handle
            if self.lifecycle.state == DRAINING:
                handle._finish(
                    REJECTED, now, error="server draining; retry elsewhere",
                    retryable=True, retry_after=1.0,
                )
                return handle
            if len(self._pending_imports) >= self.max_queue:
                # each queued import pins a whole deserialized span in host
                # memory — the same backpressure bound as submit(), so a
                # fleet-wide migrate_all onto one target gets honest 503s
                # (shippers fail over) instead of ballooning this replica
                handle._finish(
                    REJECTED, now,
                    error=f"import queue full ({self.max_queue} waiting)",
                    retryable=True, retry_after=1.0,
                )
                return handle
            self._pending_imports.append((handle, payload))
        return handle

    @staticmethod
    def _seed_imported_ledger(handle: RequestHandle, payload: Dict[str, Any]) -> None:
        """Continue the shipped stream's cumulative cost ledger: counters
        carry over verbatim, the source's ms split becomes this handle's
        base, and the page crossing itself counts as one migration.
        Defensive coercion — a version-skewed peer's ledger must degrade
        to zeros, never fault the import."""
        led = payload.get("ledger")
        if isinstance(led, dict):
            for key in handle.ledger:
                try:
                    handle.ledger[key] = int(led.get(key, 0) or 0)
                except (TypeError, ValueError):
                    pass
            for key in handle._ledger_ms_base:
                try:
                    handle._ledger_ms_base[key] = float(led.get(key, 0.0) or 0.0)
                except (TypeError, ValueError):
                    pass
        handle.ledger["migrations"] += 1
        hop = payload.get("hop")
        if hop is not None:
            try:
                # the attach dispatch is the NEXT hop after the ship
                handle.trace_hop = int(hop) + 1
            except (TypeError, ValueError):
                pass

    # graftlint: hot-path
    def _service_imports(self) -> None:
        """Tick-thread side of migration RECEIVE: place queued imports —
        allocate pages, scatter the span in, install the decode carry (or
        re-arm the prefill job), and continue. Imports outrank normal
        admission (their tokens are already paid for elsewhere); one that
        cannot fit yet waits at the head, FIFO, exactly like paged
        admission backpressure. Entries are POPPED under the lock (never
        peeked): a concurrent ``begin_drain`` snapshot can therefore never
        hold the same handle this thread is placing — the requeue path
        re-checks drain state under the same lock, so a drained handle is
        finished exactly once, by exactly one side."""
        while True:
            with self._lock:
                if not self._pending_imports:
                    return
                handle, payload = self._pending_imports.popleft()
            now = self.now()
            if handle.status in _FINISHED:
                continue  # an abort beat us to it; nothing to place
            if handle._cancel.is_set():
                self.stats["cancelled"] += 1
                handle._finish(CANCELLED, now)
                continue
            if (
                handle.request.deadline is not None
                and now > handle.request.deadline
            ):
                self.stats["expired_queued"] += 1
                handle._finish(
                    EXPIRED, now, error="deadline expired awaiting import"
                )
                continue
            wait = not self.slots.free_count
            if not wait:
                total_blocks = self.slots.blocks_for(
                    self._total_need_tokens(handle.request)
                )
                short = total_blocks - self.slots.pool.available
                if short > 0 and self._prefix_cache is not None and len(
                    self._prefix_cache
                ):
                    self.stats["page_faults"] += 1
                    self.stats["pages_reclaimed"] += self._prefix_cache.reclaim(
                        short
                    )
                wait = total_blocks > self.slots.pool.available
            if not wait and self._place_import(handle, payload):
                continue
            # cannot place yet (no slot / pool pressure / pool raced away):
            # back to the HEAD — unless a drain/abort landed meanwhile, in
            # which case the queue we'd rejoin has already been flushed
            with self._lock:
                if self._dead is None and self.lifecycle.state != DRAINING:
                    self._pending_imports.appendleft((handle, payload))
                    return
            handle._finish(
                REJECTED, now, error="server draining; retry elsewhere",
                retryable=True, retry_after=1.0,
            )
            return

    # graftlint: hot-path
    def _place_import(self, handle: RequestHandle, payload: Dict[str, Any]) -> bool:
        """Materialize one import into a slot. True when the handle left
        the pending queue (placed OR terminally failed); False to retry
        next tick."""
        slot = self.slots.acquire()
        now = self.now()
        # graftlint: allow[host-sync-in-hot-path] reason=wire-payload fields are host ints/numpy (json header + frombuffer), never device values
        fill, veto_val, n_blocks = int(payload.get("fill", 0)), int(payload.get("veto", -1)), int(payload["n_blocks"])
        try:
            ok = self.slots.import_page_span(slot, {
                "page_size": payload["page_size"],
                "n_blocks": n_blocks,
                "leaves": {
                    k: v for k, v in payload["leaves"].items()
                    if not k.startswith("carry/")
                },
            })
        except Exception as exc:  # geometry/dtype skew fails ONE import, never the tick thread
            self.slots.release([slot])
            handle._finish(
                FAILED, now, error=f"import rejected: {exc}", retryable=True,
            )
            return True
        if not ok:
            self.slots.release([slot])
            return False  # pool raced away; retry next tick
        try:
            self.slots.reserve(slot, self._total_need_tokens(handle.request))
            handle.status = RUNNING
            handle.admitted_at = now
            self._h_queue_wait.observe(now - handle.submitted_at)
            if payload["kind"] == "prefill":
                self.slots.set_cursor(slot, fill)
                self._prefilling[slot] = _PrefillJob(handle, fill=fill)
            else:
                leaves = payload["leaves"]
                self.slots.set_cursor(slot, len(handle.request.prompt))
                args = (
                    self._last_logits, self._gen_mask, self._rngs,
                    self._veto, jnp.int32(slot),
                    jnp.asarray(leaves["carry/last_logits"], jnp.float32),
                    jnp.asarray(leaves["carry/gen_mask"], jnp.bool_),
                    jnp.asarray(leaves["carry/rng"], jnp.uint32),
                    jnp.int32(veto_val),
                )
                self._last_logits, self._gen_mask, self._rngs, self._veto = _in_mesh(
                    self.mesh, _install_import, *args
                )
                handle.prefill_done_at = now
                self._active[slot] = _ActiveSlot(handle)
                self.stats["peak_occupancy"] = max(
                    self.stats["peak_occupancy"], self.active_count
                )
        except Exception as exc:  # bad carry shapes fail ONE import, never the tick thread
            self._prefilling.pop(slot, None)
            self._active[slot] = None
            self.slots.release([slot])
            handle._finish(
                FAILED, now, error=f"import install failed: {exc!r}",
                retryable=True,
            )
            return True
        self.stats["migrations_in"] += 1
        self._event(
            "stream_imported", request_id=handle.rid, kind=payload["kind"],
            blocks=n_blocks,
        )
        return True

    def _grow_decode_pages(self) -> None:
        """Paged: extend each decoding slot's block table to cover this
        tick's writes (cursor + 1, plus the draft window when speculating),
        with a copy-on-write guard on the first written block (chunk/page
        alignment makes a shared cursor page unreachable; the guard keeps
        that a checked invariant). A slot the pool genuinely cannot cover —
        reservations make that a bookkeeping bug — preempts retryably
        rather than corrupting a neighbor."""
        span = 1 + self.draft_k
        victims: List[int] = []
        for slot, act in enumerate(self._active):
            if act is None:
                continue
            cursor = len(act.handle.request.prompt) + act.emitted
            if not self._ensure_pages_or_reclaim(slot, cursor + span):
                victims.append(slot)
                continue
            if not self.slots.cow(slot, cursor // self.page_size):
                victims.append(slot)
        if victims:
            now = self.now()
            for slot in victims:
                self.stats["preemptions"] += 1
                self._active[slot].handle._finish(
                    FAILED, now,
                    error="KV page pool exhausted; request preempted (retryable)",
                    retryable=True,
                )
            self._retire(victims)
            self._event("page_preemption", slots=len(victims), phase="decode")

    # ------------------------------------------------------ tick supervision

    def _event(self, name: str, **fields) -> None:
        """Resilience incident -> the same JSONL/wandb timeline the training
        stack writes (MetricsLogger.event), keyed by scheduler tick — and
        into the flight recorder's ring, so a later dump carries the event
        context even when no MetricsLogger is attached."""
        self.flight.event(name, tick=self._tick, **fields)
        if self.metrics is not None:
            self.metrics.event(name, step=self._tick, **fields)

    def _on_tick_fault(self, exc: Exception) -> None:
        """One decode tick failed: fail ONLY the slots it poisoned (their
        clients get a retryable error event), reallocate the device state
        the tick may have invalidated, and let the breaker escalate —
        DEGRADED + a freshly jitted step after ``threshold`` consecutive
        faults, a loud abort after ``max_rebuilds`` consecutive rebuilds."""
        self.stats["tick_faults"] += 1
        now = self.now()
        failed = [s for s, a in enumerate(self._active) if a is not None]
        for slot in failed:
            self._active[slot].handle._finish(
                FAILED, now,
                error=f"decode tick failed (retryable): {exc!r}",
                retryable=True,
            )
            # HOST-only cleanup — _retire would run the jitted index reset
            # over self.slots.cache, whose buffers the faulted (donating)
            # call may have deleted, re-raising INSIDE the fault handler and
            # killing the scheduler; _rebuild_device_state below replaces
            # the whole PagedKVCache (free list included) instead
            self._active[slot] = None
        # mid-prefill slots die with the tick too: the rebuild below
        # replaces the cache tree their half-filled rows live in (the
        # donating decode step made every shared buffer suspect)
        for slot in sorted(self._prefilling):
            job = self._prefilling.pop(slot)
            job.handle._finish(
                FAILED, now,
                error=f"decode tick failed (retryable): {exc!r}",
                retryable=True,
            )
            failed.append(slot)
        self._event("tick_fault", error=repr(exc), slots_failed=len(failed))
        if self._breaker.record_fault():
            self.stats["breaker_trips"] += 1
            self._rebuilds_since_recovery += 1
            if self._rebuilds_since_recovery > self.max_rebuilds:
                # a fault that survives this many CONSECUTIVE rebuilds is
                # structural, not transient — fail everything outstanding
                # (any driver, not just run(), must leave no handle hanging)
                # and escalate so the replica dies loudly; the orchestrator
                # owns restarts, not this loop
                reason = (
                    f"engine faulted through {self.max_rebuilds} rebuilds; "
                    f"last error: {exc!r}"
                )
                self._abort(reason)
                raise RuntimeError(reason) from exc
            self.lifecycle.to(
                DEGRADED,
                reason=f"breaker open after {self._breaker.threshold} faults",
            )
            self._event("breaker_trip", trips=self.stats["breaker_trips"])
            # post-mortem without verbose logging: the last N ticks of
            # context (summaries, events, span tail) land in the run dir
            # the moment the breaker opens, while the evidence is still in
            # the ring
            self.flight.dump(
                "breaker_open",
                extra={"error": repr(exc), "tick": self._tick,
                       "trips": self.stats["breaker_trips"]},
            )
            # the executable itself is suspect only once faults PERSIST:
            # swap in a privately jitted step on each trip (the spec step
            # is the same executable family — swap it with its twin)
            self._fused = _jit_fused_step()
            self._spec = _jit_spec_step()
        # device buffers are suspect after EVERY fused-call fault, threshold
        # or not: the step donates logits/cache/masks/rngs, so an exception
        # after dispatch leaves them deleted or half-written — reusing them
        # would fail the NEXT tick's fresh admissions too (blast radius must
        # stay at THIS tick's slots)
        self._rebuild_device_state()

    def _rebuild_device_state(self) -> None:
        """Reallocate every device buffer the tick thread owns; nothing from
        a suspect tick is reused. Host state (queue, stats, lifecycle) and
        params are untouched. A fresh ``PagedKVCache`` means a fresh page
        pool AND a fresh allocator/refcount state — the pool reinitializes
        wholesale, never patched."""
        self.slots = PagedKVCache(self.model, self.n_slots, mesh=self.mesh)
        V = self.cfg.vocab_size
        self._last_logits = jnp.zeros((self.n_slots, V), jnp.float32)
        self._gen_mask = jnp.zeros((self.n_slots, V), jnp.bool_)
        self._rngs = jnp.stack([jax.random.PRNGKey(0)] * self.n_slots)
        self._veto = jnp.full((self.n_slots,), -1, jnp.int32)
        self._prefill_touched = self._chunk_in_flight = None
        self._active = [None] * self.n_slots
        self._prefilling.clear()
        if self._prefix_cache is not None:
            # conservative: cached entries trace to earlier, clean ticks,
            # but re-deriving which survived a faulted tick is not worth
            # wrong K/V if the reasoning ever rots — cold misses rebuild
            # the cache. The old index refcounts into the DEAD pool;
            # rebuild it against the fresh one instead of flushing into it.
            self._prefix_cache = self._make_prefix_cache()
        self._event("engine_rebuilt")

    # ----------------------------------------------------------------- drain

    @property
    def draining(self) -> bool:
        return self.lifecycle.state == DRAINING

    def begin_drain(self, deadline_s: Optional[float] = 30.0) -> bool:
        """Stop admission and start finishing in-flight generations
        (SIGTERM maps here). Queued requests finish immediately as
        retryable rejections (their slot time belongs to requests already
        decoding); actives run to completion until ``deadline_s``, after
        which ``poll_drain`` force-finishes them. Thread-safe; idempotent."""
        now = self.now()
        if not self.lifecycle.to(DRAINING, reason="drain requested"):
            return False
        with self._lock:
            self._drain_started = now
            self._drain_deadline = (
                now + deadline_s if deadline_s is not None else None
            )
            queued = list(self._queue)
            self._queue.clear()
            pending, self._pending_imports = (
                list(self._pending_imports), deque()
            )
        queued = queued + [h for h, _ in pending]
        for handle in queued:
            self.stats["rejected_draining"] += 1
            handle._finish(
                REJECTED, now, error="server draining; retry elsewhere",
                retryable=True,
                retry_after=max(1.0, deadline_s) if deadline_s else 1.0,
            )
        self._event(
            "drain_begin", queued_rejected=len(queued), active=self.active_count
        )
        return True

    def poll_drain(self) -> bool:
        """Called between ticks while draining: True once the engine has
        fully drained (or the deadline forced it) and is STOPPED."""
        if not self.draining:
            return self.lifecycle.state == STOPPED
        now = self.now()
        if (
            self.active_count == 0
            and not self._prefilling
            and self.queue_depth == 0
            and not self._migrating
            and not self._pending_imports
        ):
            self._finish_drain(forced=0)
            return True
        if self._drain_deadline is not None and now > self._drain_deadline:
            forced = [s for s, a in enumerate(self._active) if a is not None]
            for slot in forced:
                self._active[slot].handle._finish(
                    FAILED, now,
                    error="drain deadline exceeded; generation force-finished",
                    retryable=True,
                )
            self._retire(forced)
            still_prefilling = sorted(self._prefilling)
            for slot in still_prefilling:
                job = self._prefilling.pop(slot)
                job.handle._finish(
                    FAILED, now,
                    error="drain deadline exceeded; generation force-finished",
                    retryable=True,
                )
            self.slots.release(still_prefilling)
            forced_total = len(forced) + len(still_prefilling)
            self.stats["drain_forced"] += forced_total
            self._finish_drain(forced=forced_total)
            return True
        return False

    def _finish_drain(self, forced: int) -> None:
        now = self.now()
        self.drain_latency_s = (
            now - self._drain_started if self._drain_started is not None else 0.0
        )
        with self._lock:
            self._dead = "engine drained (stopped)"
        self.lifecycle.to(STOPPED, reason="drained")
        self._event(
            "drain_done", forced=forced, drain_latency_s=self.drain_latency_s
        )
        self._profiler.abort()  # never leave the process-global trace running
        self.flight.dump(
            "drain",
            extra={"forced": forced, "drain_latency_s": self.drain_latency_s},
        )
        self.export_trace()

    # ------------------------------------------------------------ hot reload

    def _prepare_weights(self, tree) -> Tuple[Any, Dict[str, int]]:
        """``tree`` in its serving form, and the two gauges that say what
        that took: the bytes now held, and how many of them are the result
        of a conversion (0 for a model stored in its compute dtype). Runs
        at start and on the reload thread, never on the tick thread."""
        with self.tracer.span("prepare_weights", "engine"):
            held = jax.block_until_ready(serving_params(self.model, tree))
        src, out = jax.tree.leaves(tree), jax.tree.leaves(held)
        return held, {
            "weights_bytes_held": sum(x.nbytes for x in out),
            "weights_bytes_converted_at_load": sum(
                x.nbytes for x, was in zip(out, src) if x is not was
            ),
        }

    def reload_params(self, source) -> Dict[str, Any]:
        """Stage a standby param tree and swap it in between ticks — no slot
        is retired; in-flight generations continue on the new weights from
        their next token.

        ``source`` is a param tree or a zero-arg callable returning one
        (e.g. a lambda over ``checkpoint.import_params_msgpack``). Called
        OFF the tick thread (HTTP handler, SIGHUP thread): the load, the
        eval_shape validation against the SOURCE form the engine was built
        from (so a float32 checkpoint reloads into an engine that holds
        bfloat16) and the conversion to the serving form happen here; the
        tick thread only flips a reference. A corrupt or mismatched
        artifact raises ``ReloadError`` and the engine keeps serving the
        old weights, READY throughout."""
        try:
            tree = source() if callable(source) else source
            if self._chaos is not None:
                tree = self._chaos.corrupt_reload(tree)
            validate_reload(self._source_spec, tree)
            tree = jax.tree.map(jnp.asarray, tree)
            # runtime-owned buffers before the swap: msgpack/orbax restores
            # and device_put can hand back zero-copy host views, and a
            # donating consumer of such a buffer corrupts the heap on this
            # image's jax (see jax_compat.ensure_donatable). Under a TP
            # mesh the caller's loader must pre-shard (shard_for_inference)
            # exactly as serve.py does at startup.
            from zero_transformer_tpu.utils.jax_compat import ensure_donatable

            staged = self._prepare_weights(ensure_donatable(tree))
        except ReloadError as exc:
            self.stats["reloads_rejected"] += 1
            self._event("reload_rejected", error=str(exc))
            raise
        except Exception as exc:
            self.stats["reloads_rejected"] += 1
            self._event("reload_rejected", error=repr(exc))
            raise ReloadError(f"reload failed to load: {exc!r}") from exc
        swap_event = threading.Event()
        with self._lock:
            if self._dead is not None:
                # no tick thread will ever swap this in — fail fast (409)
                # instead of letting the admin caller block a full swap
                # timeout for a misleading "staged"
                self.stats["reloads_rejected"] += 1
                raise ReloadError(f"engine is not serving: {self._dead}")
            # a superseded (staged-but-unswapped) predecessor never serves:
            # its event stays unset and its caller truthfully gets "staged,
            # not swapped" rather than credit for a swap that was B's
            self._pending_params = staged + (swap_event,)
            self._last_reload_event = swap_event
        return {
            "staged": True,
            "swapped": swap_event,  # PER-RELOAD: set only when THIS tree serves
            "reloads": self.stats["reloads"],
        }

    def _swap_pending_params(self) -> None:
        """Tick-thread side of reload: flip the param reference at a tick
        boundary, so prefill and the fused step inside one tick always see
        ONE tree. Active slots keep their cache rows — nothing retires."""
        with self._lock:
            pending, self._pending_params = self._pending_params, None
        if pending is None:
            return
        self.params, self._weights_bytes, swap_event = pending
        self.stats["reloads"] += 1
        if self._prefix_cache is not None:
            # invalidation-on-reload: cached K/V spans embody the OLD
            # weights — serving them under the new tree would garble every
            # shared-prefix request. Flushed at the same tick boundary the
            # params flip, so no tick ever mixes the two.
            flushed = self._prefix_cache.flush()
            if flushed:
                self._event("prefix_cache_flushed", entries=flushed)
        # slots MID-chunked-prefill restart from token zero: their rows
        # hold old-weight K/V for [0, fill), and finishing the prompt under
        # the new tree would (a) decode from weight-mixed prompt K/V and
        # (b) bank those mixed spans into the just-flushed prefix cache,
        # poisoning every later shared-prefix request. Re-prefilling a few
        # chunks on a rare admin event is cheap; the request then matches
        # generate() under the NEW weights exactly. (Decoding slots keep
        # the PR 3 contract: they continue on the new weights from their
        # next token, nothing retires.)
        for slot, job in self._prefilling.items():
            job.fill = 0
            job.handle.prefix_hit_tokens = 0
            # the slot may map SHARED pages from its pre-reload prefix
            # hit; re-prefilling under the new weights must not write
            # into pages other slots still read — drop every page and
            # refill fresh (the full worst case re-reserves)
            self.slots.reset_slot_pages(slot)
            self.slots.reserve(
                slot, self._total_need_tokens(job.handle.request)
            )
        swap_event.set()
        self._event("reload_swapped", reloads=self.stats["reloads"])

    def wait_reload(self, timeout: Optional[float] = 10.0) -> bool:
        """Block until the most recently STAGED reload has swapped in."""
        event = self._last_reload_event
        return event.wait(timeout=timeout) if event is not None else False

    # ------------------------------------------------------------- scheduler

    def run(self, stop: threading.Event, idle_sleep: float = 0.001) -> None:
        """Scheduler loop for a background thread: step until ``stop`` or a
        completed drain.

        A non-tick exception (tick faults are supervised inside ``step``)
        would otherwise kill the thread SILENTLY: every in-flight handle
        waits forever on a 'done' event that never comes while /healthz
        keeps answering — a hung total outage. Fail loudly instead: finish
        every active and queued handle as ``failed`` (so blocked clients
        unblock with the error), then re-raise."""
        self.lifecycle.to(READY, reason="scheduler started")
        while not stop.is_set():
            try:
                busy = self.step()
            except Exception as exc:
                self._abort(f"scheduler died: {exc!r}")
                raise
            if self.draining and self.poll_drain():
                return  # drained clean: nothing queued or active remains
            if not busy:
                # every instant of this thread is under ``tick`` or ``idle``
                # on the profiler's side; the ring keeps no idle spans (a
                # parked engine would fill it at a thousand a second)
                with self.tracer.span("idle", "engine") as idle_span:
                    idle_span.discard()
                    time.sleep(idle_sleep)
        # graceful stop: anything still queued or mid-decode will never get
        # another tick — finish it as failed so blocked consumers unblock
        self._abort("engine stopped")

    def _abort(self, reason: str) -> None:
        """Terminate every outstanding request with ``failed`` and mark the
        engine dead so later ``submit()`` calls fail fast too."""
        now = self.now()
        self.lifecycle.to(STOPPED, reason=reason)
        with self._lock:
            self._dead = reason
            queued = list(self._queue)
            self._queue.clear()
        for handle in queued:
            handle._finish(FAILED, now, error=reason)
        for slot, act in enumerate(self._active):
            if act is not None:
                act.handle._finish(FAILED, now, error=reason)
                self._active[slot] = None
        for slot in sorted(self._prefilling):
            self._prefilling.pop(slot).handle._finish(FAILED, now, error=reason)
        with self._lock:
            migrating = list(self._migrating.values())
            self._migrating.clear()
            pending, self._pending_imports = (
                list(self._pending_imports), deque()
            )
        for handle in migrating:
            handle._finish(FAILED, now, error=reason, retryable=True)
        for handle, _ in pending:
            handle._finish(FAILED, now, error=reason, retryable=True)
        self._profiler.abort()
        if "drained" not in reason:
            # a drain already dumped through _finish_drain; every OTHER path
            # here is an outage worth a post-mortem window
            self.flight.dump("abort", extra={"reason": reason})
            self.export_trace()

    def run_until_idle(self, max_ticks: int = 100_000) -> None:
        """Drive the scheduler synchronously until queue and slots drain
        (test / batch harness; raises if it fails to converge)."""
        for _ in range(max_ticks):
            if not self.step() and self.queue_depth == 0:
                return
        raise RuntimeError(f"engine not idle after {max_ticks} ticks")

    # --------------------------------------------------------------- metrics

    def metrics_snapshot(self) -> Dict[str, float]:
        """Aggregate serving metrics (milliseconds for latencies)."""
        elapsed = max(self.now() - self._started, 1e-9)
        snap: Dict[str, float] = {
            "tokens_per_sec": self.stats["tokens_out"] / elapsed,
            "slot_occupancy": self.active_count,
            "queue_depth": len(self._queue),
            "state": self.lifecycle.state,
            "uptime_s": self.lifecycle.uptime_s,
            "breaker_open": self._breaker.open,
            "itl_ewma_ms": (self._itl_ewma.value or 0.0) * 1e3,
            # prefill-path visibility: the chunk budget in force and how
            # many slots are mid-prefill
            "prefill_chunk": self.prefill_chunk,
            "prefilling": len(self._prefilling),
            # page-pool + speculation gauges
            "draft_k": self.draft_k,
            "page_pool_util": self.slots.page_pool_util,
            "page_pool_peak": self.slots.pool.peak_in_use,
            "cow_copies": self.slots.cow_copies,
            # what ONE cached position costs in every K/V entry (a looped
            # stack keeps n_loops a layer): the number to size
            # page_pool_tokens with
            "kv_bytes_per_token": kv_bytes_per_token(self.cfg),
            # recurrent state beside the pages (0 for a model that only
            # attends): what a slot keeps whatever its length, the pool of
            # all slots, and the slots a request holds now
            "state_bytes_per_slot": self._state_bytes_per_slot,
            "state_pool_bytes": self.slots.state_pool_bytes,
            "state_rows_in_use": self.state_rows_in_use,
            # the weights as held (the serving form) and how many of those
            # bytes a conversion at load made
            **self._weights_bytes,
            "acceptance_rate": (
                self.stats["accepted_tokens"] / self.stats["draft_tokens"]
                if self.stats["draft_tokens"]
                else 0.0
            ),
            # is the paged-attention kernel compiled into the decode
            # program (vs the gather fallback)?
            "kernel_paged_attention": int(
                self._paged_kernel and not self.cfg.latent_attention
            ),
            "kernel_latent_attention": int(
                self._paged_kernel and self.cfg.latent_attention
            ),
            # disaggregation / migration gauges
            "role": self.role,
            "free_pages": self.free_pages,
            "migrations_in_flight": self._migrations_in_flight,
            "pending_imports": len(self._pending_imports),
        }
        # compile-family sanitizer gauges: distinct jit signatures seen per
        # labeled dispatch site vs its declared bound; a nonzero violation
        # count is the "serving got slow" compile-storm smoking gun
        for site in (self._ds_decode, self._ds_prefill, self._ds_spec,
                     self._ds_paged):
            short = site.name.rsplit(".", 1)[-1]
            snap[f"dispatch_{short}_signatures"] = site.distinct
            snap[f"dispatch_{short}_violations"] = site.violations
        if self._prefix_cache is not None:
            snap.update(self._prefix_cache.stats())
        else:
            snap.update({
                "prefix_hits": 0, "prefix_misses": 0, "prefix_stores": 0,
                "prefix_evictions": 0, "prefix_entries": 0,
                "prefix_hit_rate": 0.0,
            })
        # percentiles straight from the fixed-bucket histograms: O(buckets)
        # per quantile, no sample-list copy, no scheduler lock (the pre-PR7
        # deque sort under self._lock was the known scrape cost here)
        for name, hist in (
            ("ttft_ms", self._h_ttft),
            ("itl_ms", self._h_itl),
            ("itl_decode_ms", self._h_itl_decode),
        ):
            for q in (50, 90, 99):
                snap[f"{name}_p{q}"] = hist.quantile(q / 100.0) * 1e3
        for k in (
            "submitted", "completed", "rejected_queue_full", "rejected_invalid",
            "expired_queued", "expired_decoding", "cancelled", "tokens_out",
            "peak_occupancy", "peak_queue_depth",
            "tick_faults", "poisoned_slots", "breaker_trips", "shed_infeasible",
            "rejected_draining", "drain_forced", "reloads", "reloads_rejected",
            "prefill_chunks", "prefill_faults", "prefill_faults_escalated",
            "expired_prefilling",
            "prefill_rows_live", "prefill_rows_computed",
            "page_faults", "pages_reclaimed", "preemptions",
            "page_waits", "loop_passes",
            "kernel_pages_live", "kernel_pages_table",
            "moe_tokens_routed", "moe_expert_load_max", "moe_expert_load_mean",
            "state_resets", "state_rows_skipped", "state_refusals_prefix_cache",
            "state_refusals_page_span",
            "spec_ticks", "draft_tokens", "accepted_tokens",
            "migrations_out", "migrations_in", "migration_failures",
            "prefill_handoffs", "import_replayed_tokens",
            "rejected_quota", "rejected_brownout", "shed_lower_class",
            "preempted_for_class", "brownout_transitions", "stalled_streams",
        ):
            snap[k] = self.stats[k]
        snap["brownout_rung"] = self._brownout_rung
        snap["queue_by_class"] = self._queue.counts()
        return snap

    def prometheus_text(self) -> str:
        """Prometheus text exposition (``text/plain; version=0.0.4``) of the
        registry: histograms directly, host counters/gauges through
        scrape-time callbacks — the tick thread never pays for exposition."""
        return self.registry.render()

    def _register_exports(self) -> None:
        """Wire the host-side ``stats`` counters and live gauges into the
        Prometheus registry as scrape-time callbacks (the hot path keeps
        its plain-int increments; only a scrape pays the read)."""
        reg = self.registry
        for key, help_text in (
            ("submitted", "Requests submitted (accepted + rejected)"),
            ("completed", "Requests finished with status done"),
            ("rejected_queue_full", "Admission rejections: queue full"),
            ("rejected_invalid", "Admission rejections: invalid request"),
            ("rejected_draining", "Admission rejections while draining"),
            ("shed_infeasible", "Deadline-infeasible sheds at admission"),
            ("expired_queued", "Deadline expiries while queued"),
            ("expired_prefilling", "Deadline expiries during prefill"),
            ("expired_decoding", "Deadline expiries mid-decode"),
            ("cancelled", "Client cancellations honored"),
            ("tokens_out", "Tokens emitted to clients"),
            ("tick_faults", "Supervised decode-tick faults"),
            ("poisoned_slots", "Slots retired by the non-finite guard"),
            ("breaker_trips", "Circuit-breaker trips (DEGRADED + rebuild)"),
            ("drain_forced", "Generations force-finished at drain deadline"),
            ("reloads", "Hot weight reloads swapped in"),
            ("reloads_rejected", "Hot weight reloads rejected"),
            ("prefill_chunks", "Chunk-prefill row dispatches"),
            ("prefill_faults", "Supervised chunk-prefill faults"),
            ("prefill_faults_escalated",
             "Chunk-prefill faults that had consumed the donated cache (tick faults)"),
            ("prefill_rows_live", "Chunk-prefill program rows that prefilled a slot"),
            ("prefill_rows_computed",
             "Chunk-prefill program rows computed (padding included)"),
            ("page_faults", "Page-pool exhaustions that reclaimed prefix pages"),
            ("pages_reclaimed", "Prefix-cache pages reclaimed under pressure"),
            ("preemptions", "Requests preempted for KV pages (last resort)"),
            ("page_waits",
             "Admission attempts deferred for KV pages while a slot was free"),
            ("loop_passes", "Model passes run by decode ticks (ticks x n_loops)"),
            ("kernel_pages_live",
             "KV pages of the rows' live extents, summed over decode ticks"),
            ("kernel_pages_table",
             "Block-table entries handed to decode ticks (slots x blocks)"),
            ("moe_tokens_routed",
             "(row, expert) pairs routed by decode ticks, over the routed layers"),
            ("moe_expert_load_max",
             "Rows of the busiest expert, summed over decode ticks and routed layers"),
            ("moe_expert_load_mean",
             "Mean rows an expert, summed over decode ticks and routed layers"),
            ("state_resets",
             "First prefill chunks: a slot's recurrent state read as zeros"),
            ("state_rows_skipped",
             "Slots whose recurrent state decode ticks left in HBM, unmoved"),
            ("state_refusals_prefix_cache",
             "Prefix indexes asked for and not built: recurrent state"),
            ("state_refusals_page_span",
             "Stream exports / imports refused: recurrent state"),
            ("spec_ticks", "Speculative decode ticks"),
            ("draft_tokens", "Draft tokens proposed"),
            ("accepted_tokens", "Draft tokens accepted by verify"),
            ("migrations_out", "Streams shipped to another replica"),
            ("migrations_in", "Migrated streams imported and continued"),
            ("migration_failures", "Ship failures (fell back to recompute)"),
            ("prefill_handoffs", "Disaggregated prefill-to-decode handoffs"),
            ("import_replayed_tokens",
             "Tokens recomputed by imported streams (0 by construction)"),
            ("rejected_quota", "Admission rejections: tenant quota exhausted"),
            ("rejected_brownout",
             "Admission rejections: brownout suspended the class"),
            ("shed_lower_class",
             "Queue-full sheds that evicted a lower QoS class"),
            ("preempted_for_class",
             "Running streams preempted for a waiting higher class"),
            ("brownout_transitions", "Brownout rung transitions"),
            ("stalled_streams",
             "Streams retired because the client stalled (emit overflow)"),
        ):
            reg.counter_func(
                f"serve_{key}", help_text,
                (lambda k=key: self.stats[k]),
            )
        reg.gauge_func(
            "serve_queue_depth", "Requests waiting for a slot",
            lambda: len(self._queue),
        )
        reg.gauge_func(
            "serve_brownout_rung",
            "Brownout rung index (0=normal .. 3=suspend_batch)",
            lambda: BROWNOUT_RUNGS.index(self._brownout_rung),
        )
        reg.gauge_func(
            "serve_slot_occupancy", "Slots actively decoding",
            lambda: self.active_count,
        )
        reg.gauge_func(
            "serve_prefilling_slots", "Slots mid-chunked-prefill",
            lambda: len(self._prefilling),
        )
        reg.gauge_func(
            "serve_state_pool_bytes",
            "Bytes of recurrent state the cache holds beside its pages",
            lambda: self.slots.state_pool_bytes,
        )
        reg.gauge_func(
            "serve_state_rows_in_use",
            "Slots whose recurrent state a request holds (decoding + prefilling)",
            lambda: self.state_rows_in_use,
        )
        reg.gauge_func(
            "serve_slots", "Configured decode slots", lambda: self.n_slots
        )
        reg.gauge_func(
            "serve_breaker_open", "1 while the circuit breaker is open",
            lambda: 1 if self._breaker.open else 0,
        )
        reg.gauge_func(
            "serve_uptime_seconds", "Engine lifetime on its own clock",
            lambda: self.lifecycle.uptime_s,
        )
        reg.gauge_func(
            "serve_itl_ewma_seconds", "Shedding's measured ITL EWMA",
            lambda: self._itl_ewma.value or 0.0,
        )
        reg.gauge_func(
            "serve_page_pool_util", "KV page pool utilization",
            lambda: self.slots.page_pool_util,
        )
        # page-pool pressure as first-class scrape families (pre-PR12 a
        # router could only see free_pages by polling /healthz)
        reg.gauge_func(
            "serve_free_pages",
            "Spare KV capacity (free pool pages)",
            lambda: self.free_pages,
        )
        reg.counter_func(
            "serve_cow_copies",
            "Copy-on-write page copies (shared page written post-import/share)",
            lambda: self.slots.cow_copies,
        )
        reg.gauge_func(
            "serve_migrations_in_flight",
            "Streams exported and awaiting their ship acknowledgement",
            lambda: self._migrations_in_flight,
        )
        reg.gauge_func(
            "serve_pending_imports",
            "Imported streams awaiting placement into a slot",
            lambda: len(self._pending_imports),
        )
        reg.gauge_func(
            "serve_prefix_cache_entries", "Prefix-cache entries resident",
            lambda: (
                len(self._prefix_cache) if self._prefix_cache is not None else 0
            ),
        )
        reg.gauge_func(
            "serve_trace_spans_dropped",
            "Spans pushed out of the bounded trace ring",
            lambda: self.tracer.dropped,
        )
        # the fleet-standard name (PR 15): same value on every process
        # (router, replicas, trainer exporter) so one dashboard query
        # covers trace-truncation honesty fleet-wide
        reg.gauge_func(
            "obs_spans_dropped",
            "Spans dropped by ring overflow (trace truncation honesty)",
            lambda: self.tracer.dropped,
        )
        # per-device HBM with max/mean rollups (None on backends without
        # memory stats — the callbacks then render no samples). One shared
        # short-TTL read per scrape: the three gauges render back to back,
        # and each hbm_device_stats() call is a memory_stats runtime query
        # PER DEVICE — tripling that per scrape is pure waste
        hbm_cache = {"t": -1.0, "v": None}

        def _hbm() -> dict:
            t = time.monotonic()
            if t - hbm_cache["t"] > 0.25:
                hbm_cache["v"] = hbm_device_stats()
                hbm_cache["t"] = t
            return hbm_cache["v"] or {}

        reg.gauge_func(
            "hbm_used_gigabytes", "Per-device HBM in use",
            lambda: [
                ({"device": str(i)}, gb)
                for i, gb in enumerate(_hbm().get("per_device_gb", []))
            ],
        )
        reg.gauge_func(
            "hbm_used_gigabytes_max", "Max HBM in use across local devices",
            lambda: _hbm().get("max_gb"),
        )
        reg.gauge_func(
            "hbm_used_gigabytes_mean", "Mean HBM in use across local devices",
            lambda: _hbm().get("mean_gb"),
        )

    # ------------------------------------------------------------- profiling

    def request_profile(self, ticks: int) -> Dict[str, Any]:
        """Stage a ``jax.profiler`` capture of the next ``ticks`` scheduler
        ticks (``POST /admin/profile`` lands here). Thread-safe staging;
        the tick thread alone starts/stops the trace. Raises RuntimeError
        while draining/stopped, without an ``obs_dir``, or when a capture
        is already in progress."""
        with self._lock:
            if self._dead is not None:
                raise RuntimeError(f"engine is not serving: {self._dead}")
            if self.lifecycle.state == DRAINING:
                raise RuntimeError(
                    "engine is draining; profile capture rejected"
                )
            info = self._profiler.request(
                ticks, name=f"serve_tick{self._tick}"
            )
        self._event("profile_requested", ticks=ticks, path=info["path"])
        return info

    @property
    def profile_active(self) -> bool:
        return self._profiler.active

    @property
    def profiles_completed(self) -> List[str]:
        return list(self._profiler.completed)

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the span ring as Perfetto/Chrome-trace JSON (default:
        ``<obs_dir>/trace_serve.json``) plus an incremental append to
        ``<obs_dir>/spans.jsonl`` beside ``metrics.jsonl``."""
        if path is None:
            if self.obs_dir is None:
                return None
            path = str(Path(self.obs_dir) / "trace_serve.json")
        out = self.tracer.write_chrome_trace(path)
        if self.obs_dir is not None:
            self.tracer.write_jsonl(Path(self.obs_dir) / "spans.jsonl")
        return out
