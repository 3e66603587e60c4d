"""Runtime compile-family sanitizer: bounded, labeled dispatch sites.

The repo's fixed-shape discipline (PR 4/6/8) says every jit dispatch site
has a BOUNDED family of cache signatures: the engine's fused decode step is
ONE program whatever the occupancy, the chunk prefill is ONE [R, C] program
whatever the prompt mix, the trainer step is ONE program for the whole run.
A regression (a shape that varies per request, a static arg that varies per
tick) silently multiplies compiles and looks like "serving got slow".

``bounded_dispatch(name, max_entries)`` creates a labeled site. The caller
``observe()``s the argument tuple right before each dispatch; the site
abstracts the args the same way jit's cache key does for the purposes we
care about — array leaves become (shape, dtype), hashable scalars keep
their value (static args select executables by value), opaque objects
collapse to their type — and counts DISTINCT signatures. Exceeding
``max_entries``:

- in strict mode (tests: ``set_strict(True)``, or env
  ``GRAFTLINT_DISPATCH=strict``): raises ``CompileFamilyExceeded`` listing
  every signature the site has seen, so the offending axis of variation is
  readable straight from the failure;
- otherwise: increments ``site.violations`` and warns ONCE per site —
  production serving must not die on an observability check.

No jax import: array leaves are duck-typed on ``.shape``/``.dtype``, so the
module stays importable from the stdlib-only lint path.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import warnings
import weakref
from typing import Any, Dict, List, Optional, Tuple

_registry_lock = threading.Lock()
_registry: "weakref.WeakSet[DispatchSite]" = weakref.WeakSet()
_strict: Optional[bool] = None


def set_strict(value: Optional[bool]) -> None:
    """Force strict mode on/off process-wide (None: defer to the
    GRAFTLINT_DISPATCH env var). Tests flip this on so a family overflow
    fails the suite instead of warning."""
    global _strict
    _strict = value


def _is_strict() -> bool:
    if _strict is not None:
        return _strict
    return os.environ.get("GRAFTLINT_DISPATCH", "") == "strict"


class CompileFamilyExceeded(RuntimeError):
    """A labeled dispatch site saw more distinct jit signatures than its
    declared bound — some argument axis varies per call that should be
    fixed-shape (or the bound is honestly wrong and must be raised WITH the
    reasoning in the call site's comment)."""

    def __init__(self, site: "DispatchSite", fresh: Tuple):
        self.site = site
        self.fresh = fresh
        lines = [
            f"dispatch site {site.name!r} exceeded its compile-family bound: "
            f"{len(site.signatures)} distinct signatures > max_entries="
            f"{site.max_entries}. Signatures seen (count x):"
        ]
        for sig, n in site.signatures.items():
            marker = "  -> NEW: " if sig == fresh else "     "
            lines.append(f"{marker}{n}x {sig}")
        super().__init__("\n".join(lines))


def _describe(x: Any, depth: int = 0) -> Any:
    """Abstract one argument into a hashable signature component, the way
    jit's cache key would distinguish it: arrays by (shape, dtype) — their
    VALUES never select an executable — scalars/strings by value (static
    args select by value), containers structurally, opaque objects by type
    (a rebuilt-but-identical model object must not look like a new
    signature)."""
    if depth > 6:
        return "..."
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("arr", tuple(shape), str(dtype))
    if x is None or isinstance(x, (bool, int, float, str, bytes)):
        return x
    if isinstance(x, (tuple, list)):
        return (type(x).__name__,) + tuple(_describe(e, depth + 1) for e in x)
    if isinstance(x, dict) or (
        not isinstance(x, type) and callable(getattr(x, "items", None))
    ):
        # dicts AND dict-like mappings (flax FrozenDict) — leaf shapes in
        # these ARE jit's cache key
        try:
            items = sorted(x.items())
        except TypeError:
            items = list(x.items())
        return ("dict",) + tuple(
            (str(k), _describe(v, depth + 1)) for k, v in items
        )
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        # registered pytree containers (flax.struct dataclasses like
        # TrainState) — collapsing these to their type would blind the
        # site to the very shapes that select the executable
        return (type(x).__name__,) + tuple(
            (f.name, _describe(getattr(x, f.name), depth + 1))
            for f in dataclasses.fields(x)
        )
    return ("obj", type(x).__name__)


class DispatchSite:
    """One labeled jit dispatch site with a declared signature bound.

    Thread-safe; cheap on the hot path (one tuple build + dict lookup; the
    describe walk touches only arg metadata, never array bytes)."""

    def __init__(self, name: str, max_entries: int):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.name = name
        self.max_entries = int(max_entries)
        self.signatures: Dict[Tuple, int] = {}
        self.violations = 0
        self._warned = False
        self._lock = threading.Lock()
        with _registry_lock:
            _registry.add(self)

    def observe(self, *args: Any, **kwargs: Any) -> None:
        """Record the signature of one dispatch. Call with the arguments
        the jitted callable is about to receive; engine-lifetime-constant
        trees (the model object, the params tree) may be omitted so the
        per-call describe walk stays O(varying args), not O(param count)."""
        sig = _describe(args) + (
            _describe(tuple(sorted(kwargs.items(), key=lambda kv: kv[0])))
            if kwargs
            else ()
        )
        with self._lock:
            count = self.signatures.get(sig)
            self.signatures[sig] = (count or 0) + 1
            if count is None and len(self.signatures) > self.max_entries:
                self.violations += 1
                if _is_strict():
                    raise CompileFamilyExceeded(self, sig)
                if not self._warned:
                    self._warned = True
                    warnings.warn(
                        f"graftlint: dispatch site {self.name!r} exceeded "
                        f"its compile-family bound ({len(self.signatures)} > "
                        f"{self.max_entries}) — shapes/statics vary per call "
                        "at a site declared fixed-shape",
                        stacklevel=2,
                    )

    def wrap(self, fn):
        """Return ``fn`` instrumented with this site (convenience for
        callables invoked directly rather than through ``_in_mesh``)."""

        def wrapped(*args, **kwargs):
            self.observe(*args, **kwargs)
            return fn(*args, **kwargs)

        wrapped.__wrapped__ = fn
        wrapped.dispatch_site = self
        return wrapped

    @property
    def distinct(self) -> int:
        return len(self.signatures)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "name": self.name,
                "max_entries": self.max_entries,
                "distinct": len(self.signatures),
                "calls": sum(self.signatures.values()),
                "violations": self.violations,
            }

    def reset(self) -> None:
        with self._lock:
            self.signatures.clear()
            self.violations = 0
            self._warned = False


def bounded_dispatch(name: str, max_entries: int) -> DispatchSite:
    """Create and register a labeled dispatch site (one per engine/trainer
    INSTANCE: the bound is about one logical site not churning compiles,
    and test processes legitimately build many differently-shaped
    engines)."""
    return DispatchSite(name, max_entries)


def all_sites() -> List[DispatchSite]:
    """Live sites, for test assertions and /metrics exports."""
    with _registry_lock:
        return sorted(_registry, key=lambda s: s.name)
