"""On-demand ``jax.profiler`` capture windows.

Two surfaces share this module:

- serving: ``POST /admin/profile {"ticks": N}`` stages a capture that the
  engine's tick thread starts at its next ``step()`` and stops N ticks
  later (``ProfileWindow`` owns the start/stop bookkeeping; only the tick
  thread touches the profiler, so there is no cross-thread start/stop
  race);
- training: ``train.py --profile-window START:LEN`` captures the step
  window [START, START+LEN) — ``parse_profile_window`` is the flag parser.

Traces land under ``<run dir>/profiles/<name>`` next to the flight-recorder
dumps, viewable in TensorBoard/XProf or ``xprof``. Every capture the program
opens goes through ``start_trace`` here: Python tracing off, the program's
live spans on the host plane as ``"<track>/<name>"`` annotations.
"""
from __future__ import annotations

import logging
import time
from pathlib import Path
from typing import Optional, Tuple

log = logging.getLogger("zero_transformer_tpu")


def parse_profile_window(spec: str) -> Tuple[int, int]:
    """``"START:LEN"`` -> (start_step, n_steps); both must be positive."""
    try:
        start_s, _, len_s = spec.partition(":")
        start, length = int(start_s), int(len_s)
    except ValueError:
        raise ValueError(
            f"--profile-window expects START:LEN (e.g. 100:20), got {spec!r}"
        ) from None
    if start < 1 or length < 1:
        raise ValueError(
            f"--profile-window START and LEN must be >= 1, got {spec!r}"
        )
    return start, length


def start_trace(directory) -> None:
    """Open a ``jax.profiler`` capture an operator can use. Python tracing is
    off: the default options trace every Python call, which slows a
    host-bound loop to a fraction of its speed and buries the capture under
    millions of host events. The host tracer stays at the level that keeps
    ``TraceAnnotation``s, so the program's live spans (``obs/spans.py``)
    land beside the device's programs. Close with
    ``jax.profiler.stop_trace()``."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    Path(directory).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(directory), profiler_options=options)


class ProfileWindow:
    """Single-owner capture window: ``request(n)`` stages it (any thread),
    ``poll()`` starts/advances/stops it (the OWNING loop thread only).

    ``poll()`` is called once per tick/step, BEFORE the work: the first call
    after a request starts the trace, each later call burns one tick of the
    budget, and the call after the budget stops the trace — so a window of
    N covers exactly N full iterations of the owning loop.
    """

    def __init__(self, directory: Optional[str], prefix: str = "capture"):
        self.directory = str(directory) if directory else None
        self.prefix = prefix
        self._pending: Optional[Tuple[int, str]] = None
        self._active: Optional[list] = None  # [target_tick, path]
        # in-progress flag spanning the WHOLE capture lifetime (staged ->
        # start_trace -> window -> stop_trace): the first start_trace can
        # block the owning thread for hundreds of ms, and a second request
        # arriving inside that window must still conflict
        self._busy = False
        self.completed: list = []  # paths of finished captures

    @property
    def active(self) -> bool:
        return self._busy

    def request(self, ticks: int, name: Optional[str] = None) -> dict:
        """Stage a capture of the next ``ticks`` loop iterations. Raises
        RuntimeError when no directory is configured or a capture is
        already staged/running (jax.profiler is single-trace)."""
        if ticks < 1:
            raise ValueError("profile ticks must be >= 1")
        if self.directory is None:
            raise RuntimeError(
                "profiling is disabled: no observability directory "
                "configured (serve --obs-dir / --metrics-dir)"
            )
        if self._busy:
            raise RuntimeError("a profile capture is already in progress")
        self._busy = True
        # graftlint: allow[wall-clock-in-span-path] reason=deliberately wall-clock — the capture DIRECTORY name is a human-readable unix stamp; no span math touches it
        stamp = name or f"{self.prefix}_{int(time.time())}"
        path = str(Path(self.directory) / "profiles" / stamp)
        self._pending = (int(ticks), path)
        return {"path": path, "ticks": int(ticks)}

    def poll(self, tick: int) -> None:
        """Advance the window (owning thread only). ``tick`` is the loop's
        monotone WORK counter — the serving engine's busy-tick index, which
        does not advance on idle spins — so a window of N covers N ticks of
        real work: started here before tick T runs, stopped when the
        counter reaches T + N."""
        if self._active is not None and tick >= self._active[0]:
            self._stop()
        if self._pending is not None and self._active is None:
            ticks, path = self._pending
            self._pending = None
            try:
                start_trace(path)
            except Exception:
                log.exception("profiler: start_trace failed (capture skipped)")
                self._busy = False
                return
            self._active = [tick + ticks, path]
            log.info("profiler: capturing %d ticks to %s", ticks, path)

    def abort(self) -> None:
        """Stop a live capture immediately (drain/abort paths): a dying
        engine must not leave the process-global profiler running."""
        self._pending = None
        if self._active is not None:
            self._stop()
        self._busy = False

    def _stop(self) -> None:
        path = self._active[1]
        self._active = None
        self._busy = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            log.exception("profiler: stop_trace failed")
            return
        self.completed.append(path)
        log.info("profiler: capture finished -> %s", path)
