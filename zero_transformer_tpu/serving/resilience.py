"""Serving-side resilience: the PR-2 fault-tolerance discipline for serving.

``resilience/`` hardened *training* against its environment (in-graph anomaly
guard, supervisor, watchdog, chaos harness); this module is the serving
counterpart, reusing those primitives instead of duplicating them:

- ``Lifecycle``: an explicit engine state machine (STARTING -> READY ->
  DEGRADED -> DRAINING -> STOPPED) that ``/healthz`` reflects with real
  status codes, so a load balancer can route around a replica that is
  warming up, sick, or draining;
- ``CircuitBreaker``: consecutive decode-tick-fault counter; at the
  threshold the engine goes DEGRADED and rebuilds its jitted step (the
  serving analogue of the supervisor's bounded-restart loop — bounded here
  by ``max_rebuilds``);
- ``ItlEwma``: the measured inter-token-latency EWMA that deadline-aware
  load shedding prices admission against (the serving analogue of
  ``anomaly.py``'s running EMAs);
- ``validate_reload``: eval_shape-style structure/shape/dtype validation of
  a standby param tree before a hot swap (a corrupt or mismatched artifact
  is rejected with the engine staying READY on the old weights);
- ``ServingChaosMonkey``: the serving extension of ``resilience.chaos`` —
  decode-fault windows, NaN-logit injection (detected by the same
  non-finite criterion as the training guard, ``anomaly.nonfinite_rows``),
  slow ticks, mid-load SIGTERM, corrupt-reload artifacts — proving all of
  the above in ``tests/test_serving_resilience.py`` (``make serve-chaos``).

Host-side only: nothing here adds device work beyond one [S]-bool
non-finite reduction per tick, fetched in the same device_get as the
sampled tokens.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from zero_transformer_tpu.resilience.chaos import ChaosMonkey, Fault

# ----------------------------------------------------------------- lifecycle

STARTING = "starting"  # constructed; scheduler loop not yet running
READY = "ready"  # serving; /healthz 200
DEGRADED = "degraded"  # breaker open after consecutive tick faults; rebuilt
DRAINING = "draining"  # admission closed; finishing in-flight generations
STOPPED = "stopped"  # terminal: drained, aborted, or stop()ed

_STATES = (STARTING, READY, DEGRADED, DRAINING, STOPPED)


class Lifecycle:
    """Thread-safe engine state machine with a transition history.

    Legal moves: STARTING -> {READY, DRAINING, STOPPED}; READY <-> DEGRADED;
    any live state -> DRAINING; DRAINING -> STOPPED only (a draining engine
    never goes back to taking traffic — restart it instead); STOPPED is
    terminal. Illegal transitions are refused (return False), not raised:
    callers race (tick thread vs signal handler vs HTTP thread) and the
    first writer wins.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._state = STARTING
        self._born = clock()
        self.history: List[Tuple[str, float, str]] = [(STARTING, self._born, "init")]

    @property
    def state(self) -> str:
        return self._state

    @property
    def uptime_s(self) -> float:
        return self._clock() - self._born

    def to(self, state: str, reason: str = "") -> bool:
        assert state in _STATES, state
        with self._lock:
            cur = self._state
            if state == cur or cur == STOPPED:
                return False
            if cur == DRAINING and state != STOPPED:
                return False
            if state == DEGRADED and cur not in (READY, STARTING):
                return False
            self._state = state
            self.history.append((state, self._clock(), reason))
            return True


# ------------------------------------------------------------ circuit breaker


class CircuitBreaker:
    """Consecutive-tick-fault breaker.

    ``record_fault`` returns True on the fault that OPENS the breaker (the
    engine's cue to go DEGRADED and rebuild); ``record_clean`` returns True
    on the clean tick that CLOSES it again (back to READY). ``cooldown``
    clean ticks are required to close — one by default: a rebuilt engine
    that survives a full fused tick has proven the executable.
    """

    def __init__(self, threshold: int = 3, cooldown: int = 1):
        if threshold < 1 or cooldown < 1:
            raise ValueError("threshold and cooldown must be >= 1")
        self.threshold = threshold
        self.cooldown = cooldown
        self.open = False
        self.consecutive_faults = 0
        self.trips = 0
        self._clean_streak = 0

    def record_fault(self) -> bool:
        self.consecutive_faults += 1
        self._clean_streak = 0
        # trip on EVERY threshold-multiple of the unbroken fault streak, not
        # only the first: an already-open breaker whose rebuilt engine keeps
        # faulting must keep tripping, or the rebuild budget (max_rebuilds)
        # can never exhaust and a structural fault spins forever
        if self.consecutive_faults % self.threshold == 0:
            self.open = True
            self.trips += 1
            return True
        return False

    def record_clean(self) -> bool:
        self.consecutive_faults = 0
        if not self.open:
            return False
        self._clean_streak += 1
        if self._clean_streak >= self.cooldown:
            self.open = False
            self._clean_streak = 0
            return True
        return False


# -------------------------------------------------------------- load shedding


class ItlEwma:
    """Measured inter-token latency EWMA (host side, one update per sample).

    ``floor_s`` is the conservative read shedding uses: admission must
    reject only PROVABLY infeasible deadlines, so the estimate is clamped
    from below by the fastest recent tick rather than inflated by a safety
    factor — overload degrades into honest 503s, never into shedding
    requests that would have made it.
    """

    def __init__(self, decay: float = 0.9, warmup: int = 8):
        self.decay = decay
        self.warmup = warmup
        self.value: Optional[float] = None
        self.count = 0
        self._min = float("inf")

    def update(self, sample: float) -> None:
        self.count += 1
        self._min = min(self._min, sample)
        if self.value is None:
            self.value = sample
        else:
            self.value = self.decay * self.value + (1.0 - self.decay) * sample

    @property
    def warm(self) -> bool:
        return self.count >= self.warmup and self.value is not None

    def floor_s(self) -> float:
        return min(self.value, self._min) if self.value is not None else 0.0


def infeasible_deadline(
    deadline: float,
    now: float,
    max_new_tokens: int,
    queue_depth: int,
    n_slots: int,
    itl: ItlEwma,
) -> bool:
    """True when ``deadline`` cannot be met even under best-case scheduling.

    Lower bound on completion: the request must decode ``max_new_tokens``
    ticks at no less than the fastest recently measured ITL, and it cannot
    start before the queue ahead of it has pushed at least
    ``queue_depth / n_slots`` tick-slots through the engine. No safety
    margin — a shed must be provable, not probable. Inert until the EWMA
    has ``warmup`` samples (a cold engine has no evidence to shed on).
    """
    if not itl.warm:
        return False
    tick = itl.floor_s()
    lower_bound = tick * (max_new_tokens + queue_depth / max(1, n_slots))
    return now + lower_bound > deadline


# ----------------------------------------------------------------- hot reload


class ReloadError(RuntimeError):
    """A standby param tree failed validation (corrupt artifact, wrong
    model); the engine stays READY on the old weights."""


def validate_reload(current: Any, candidate: Any) -> None:
    """Reject a candidate param tree whose structure, shapes, or dtypes
    differ from ``current``: the tree the engine was built from, as arrays
    or as the ``ShapeDtypeStruct`` tree the engine keeps of it (metadata
    only, nothing materializes). Raises ``ReloadError`` naming the first
    mismatch.

    Boxing-agnostic: a tree straight from ``Transformer.init`` carries flax
    ``Partitioned`` metadata boxes while a msgpack restore is plain — both
    describe the same weights, so both sides are unboxed before comparison.
    """
    try:
        from flax import linen as nn

        cur = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(current))
        new = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(candidate))
    except Exception as exc:  # not even a pytree of arrays
        raise ReloadError(f"unreadable param tree: {exc!r}") from exc
    (cur_leaves, cur_def), (new_leaves, new_def) = cur, new
    if cur_def != new_def:
        raise ReloadError(
            f"param tree structure mismatch: serving {cur_def} vs reload {new_def}"
        )
    for (path, a), (_, b) in zip(cur_leaves, new_leaves):
        a_shape, b_shape = getattr(a, "shape", None), getattr(b, "shape", None)
        a_dtype, b_dtype = getattr(a, "dtype", None), getattr(b, "dtype", None)
        if a_shape != b_shape or a_dtype != b_dtype:
            raise ReloadError(
                f"param leaf {jax.tree_util.keystr(path)} mismatch: serving "
                f"{a_shape}/{a_dtype} vs reload {b_shape}/{b_dtype}"
            )


# --------------------------------------------------------------- serving chaos


@dataclasses.dataclass
class ServeFault(Fault):
    """A serving fault (extends the training ``Fault``).

    kind: "tick_fault" | "prefill_fault" | "nan_logits" | "slow_tick" |
          "sigterm" | "corrupt_reload" | "slow_client"
    step: the scheduler TICK index the fault keys on (engine ``_tick``,
      0-based) — sigterm/slow_tick fire once at the first tick >= step;
      tick_fault / prefill_fault / nan_logits fire for ``duration``
      consecutive ticks. A prefill_fault raises inside the CHUNK-prefill
      dispatch (before the fused decode) and BEFORE the chunk program is
      handed the cache it donates, proving that such a fault fails only
      the mid-prefill slots and leaves decoding neighbors untouched (one
      that had consumed the cache is the tick's: the rule is
      ``ServingEngine._prefill_tick``'s).
      "slow_client" is a CONSUMER fault: the server's SSE pump stalls for
      ``duration`` seconds mid-stream (a reader that stopped draining its
      socket), proving the bounded emit buffer finishes the stalled
      stream retryably while neighbors stay byte-identical; ``step`` here
      is the number of events the pump delivers before stalling.
    slots: for "nan_logits", which cache rows to poison (None = every
      occupied row) — how the harness proves the guard retires ONLY the
      affected slots.
    """

    slots: Optional[Sequence[int]] = None


class ServingChaosMonkey(ChaosMonkey):
    """Fault plan for the serving engine (reuses ChaosMonkey's fired-log /
    one-shot bookkeeping). Injection points mirror where real serving
    faults enter:

    - ``on_tick``: host-side, called at the top of every supervised tick —
      raises (a poisoned decode tick), sleeps (a stalled device / GC pause),
      or SIGTERMs this process (preemption mid-load);
    - ``poison_logits``: NaN rows written into the POST-step logits, so the
      non-finite guard sees injected NaNs through the exact path a real
      numerical blow-up takes;
    - ``corrupt_reload``: mangles a standby param tree between load and
      validation, proving a bad artifact is rejected with the engine READY.
    """

    def on_tick(self, tick: int) -> None:
        for f in self._of_kind("slow_tick"):
            if not f.fired and tick >= f.step:
                self.record(f)
                time.sleep(float(f.duration))
        for f in self._of_kind("sigterm"):
            if not f.fired and tick >= f.step:
                self.record(f)
                os.kill(os.getpid(), signal.SIGTERM)
        for f in self._of_kind("tick_fault"):
            if f.step <= tick < f.step + int(f.duration):
                if not f.fired:
                    self.record(f)
                raise f.exc(f"{f.message} (decode tick {tick})")

    def client_stall_s(self, events_delivered: int) -> float:
        """SSE-pump seam ("slow_client"): called by the server's stream
        pump after each delivered event; returns the seconds the pump
        should stall (simulating a reader that stopped draining) once
        ``events_delivered`` reaches the fault's ``step``. One-shot."""
        stall = 0.0
        for f in self._of_kind("slow_client"):
            if not f.fired and events_delivered >= f.step:
                self.record(f)
                stall += float(f.duration)
        return stall

    def on_prefill_chunk(self, tick: int) -> None:
        """Called at the top of a supervised chunk-prefill dispatch: a
        "prefill_fault" in its window raises here, before the program is
        called: the path of a fault on the host's side of the dispatch."""
        for f in self._of_kind("prefill_fault"):
            if f.step <= tick < f.step + int(f.duration):
                if not f.fired:
                    self.record(f)
                raise f.exc(f"{f.message} (prefill chunk, tick {tick})")

    def poison_logits(self, tick: int, logits):
        import jax.numpy as jnp

        for f in self._of_kind("nan_logits"):
            if f.step <= tick < f.step + int(f.duration):
                if not f.fired:
                    self.record(f)
                rows = (
                    list(f.slots)
                    if f.slots is not None
                    else list(range(logits.shape[0]))
                )
                logits = logits.at[jnp.asarray(rows, jnp.int32)].set(jnp.nan)
        return logits

    def corrupt_reload(self, tree):
        faults = self._of_kind("corrupt_reload")
        if not any(not f.fired for f in faults):
            return tree
        for f in faults:
            if not f.fired:
                self.record(f)
                break
        import jax.numpy as jnp

        # truncate the first leaf: exactly what a half-written msgpack looks
        # like after flax restores it — wrong shape, same tree
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        first = leaves[0]
        leaves[0] = jnp.zeros((1,) * max(1, first.ndim), first.dtype)
        return jax.tree_util.tree_unflatten(treedef, leaves)
