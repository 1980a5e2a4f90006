"""Recompile sentinel: runtime guard for the one-train-executable rule.

The spmd_1f1b engine and TrainStep both promise exactly ONE XLA train
executable per (scaler, shapes) config — a silent retrace (a new batch
shape, a dtype drift from a preprocessing change) turns every affected
step into a multi-second compile stall and doubles HBM executable
footprint, and nothing in stock jax tells you *why* it happened. The
sentinel watches the executable count each step and, when it grows past
the expected config count, logs the offending shape/dtype delta against
the previous step's signature and bumps ``train_recompiles_total``
(always-on counter: a contract violation is counted even when the rest
of the metrics runtime is disabled).

Engines call ``observe(executables, expected, signature)`` once per
step; ``signature_of`` turns arbitrary pytrees of arrays into a
comparable (path, shape, dtype) tuple. ``watch``/``check`` wrap a bare
jax.jit function for code outside the engines.

``attach_jax_compile_hook()`` additionally taps jax.monitoring compile
events into ``jax.compiles_total`` — a coarse, framework-wide compile
odometer (best-effort: older runtimes without jax.monitoring are a
no-op). The listener is scoped to the actual ``/jax/core/compile``
event family (a bare ``"compile" in event`` substring would also count
compilation-cache bookkeeping like
``/jax/compilation_cache/compile_requests_use_cache``), and compile
*durations* — the per-phase ``*_duration`` events, or a duration kwarg
when one rides a plain event — feed the goodput ``compile`` fraction
and a ``jax.compile_secs`` histogram.
"""
from __future__ import annotations

import logging
from typing import Any, List, Optional, Tuple

from . import goodput, metrics

__all__ = ["RecompileSentinel", "signature_of", "diff_signatures",
           "attach_jax_compile_hook"]

logger = logging.getLogger("paddle_tpu.observability")


def signature_of(*trees) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
    """Flatten pytrees of arrays/Tensors into ((path, shape, dtype), ...)
    — the comparable identity a jit cache keys on."""
    import jax
    import numpy as np

    from ..framework import Tensor

    out = []
    leaves = jax.tree_util.tree_leaves_with_path(tuple(trees))
    for path, leaf in leaves:
        if isinstance(leaf, Tensor):
            leaf = leaf._data
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        out.append((jax.tree_util.keystr(path), shape, dtype))
    return tuple(out)


def diff_signatures(old, new) -> str:
    """Human-readable shape/dtype delta between two signatures."""
    if old is None:
        return "no prior signature recorded"
    o = {p: (s, d) for p, s, d in old}
    n = {p: (s, d) for p, s, d in new}
    lines = []
    for p in sorted(set(o) | set(n)):
        if p not in o:
            lines.append(f"{p}: (new input) {n[p][0]}/{n[p][1]}")
        elif p not in n:
            lines.append(f"{p}: (dropped input) was {o[p][0]}/{o[p][1]}")
        elif o[p] != n[p]:
            lines.append(
                f"{p}: {o[p][0]}/{o[p][1]} -> {n[p][0]}/{n[p][1]}")
    return "; ".join(lines) if lines else \
        "identical input signature (retrace from non-shape cause: " \
        "static args, new config, or cache eviction)"


class RecompileSentinel:
    """Per-engine watcher for the compile_count contract.

    events: list of {step, executables, expected, diff} — one entry per
    violation, newest last. The counter is the cross-engine rollup; the
    events carry the per-engine forensic detail.
    """

    def __init__(self, name: str = "train"):
        self.name = name
        # the contract counter keeps the reference's flat Prometheus
        # name so it greps identically in every exporter
        self.counter = metrics.counter(f"{name}_recompiles_total",
                                       _always=True)
        self.events: List[dict] = []
        self._last_sig = None
        self._allowed: Optional[int] = None
        self._steps = 0
        self._watched = None

    def observe(self, executables: int, expected: int = 1,
                signature: Any = None):
        """Record one step's executable count. Fires when the count
        exceeds the allowed figure (expected config count, or whatever
        higher count was already accounted for)."""
        self._steps += 1
        if self._allowed is None:
            # first step: however many executables exist now are the
            # baseline (compiles up to and including the first step are
            # the contract, not a violation)
            self._allowed = max(int(executables), int(expected))
            self._last_sig = signature
            return self
        allowed = max(self._allowed, int(expected))
        if executables > allowed:
            delta = diff_signatures(self._last_sig, signature) \
                if signature is not None else "signature not captured"
            event = {"step": self._steps, "executables": int(executables),
                     "expected": allowed, "diff": delta}
            self.events.append(event)
            self.counter.add(executables - allowed)
            # black-box breadcrumb: a recompile storm shows up in the
            # flight recorder's event stream with the shape delta that
            # caused each retrace (tpu_doctor flags the storm)
            from . import flight_recorder as _fr
            _fr.record("recompile", engine=self.name,
                       step=self._steps, executables=int(executables),
                       expected=allowed, diff=delta)
            logger.warning(
                "recompile sentinel [%s]: train executable count grew "
                "%d -> %d at step %d; input delta: %s",
                self.name, allowed, executables, self._steps, delta)
        self._allowed = max(allowed, int(executables))
        if signature is not None:
            self._last_sig = signature
        return self

    # -- bare-jit convenience ------------------------------------------------
    def watch(self, jitted):
        """Attach to a jax.jit function; pair with check(*args) after
        each call."""
        self._watched = jitted
        return jitted

    def check(self, *args, **kwargs):
        if self._watched is None:
            raise RuntimeError("watch() a jitted function first")
        sig = signature_of(tuple(args), kwargs)
        return self.observe(int(self._watched._cache_size()),
                            expected=1, signature=sig)

    @property
    def fired(self) -> int:
        return len(self.events)


_jax_hook_attached = False

# the actual compile event family (jax _src/dispatch.py constants);
# compilation-cache bookkeeping events also contain "compile" in their
# names and must NOT count as compiles
_COMPILE_EVENT_PREFIX = "/jax/core/compile"
# one executable == one backend compile; the jaxpr-trace and
# to-mlir-module phases are parts of the same compile, counted once
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent-cache bookkeeping (jax _src/compiler.py): excluded from
# the compile odometer above, but counted on their OWN meters — the
# hit ratio is the receipt that the persistent cache actually pays
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _is_compile_event(event: str) -> bool:
    return event.startswith(_COMPILE_EVENT_PREFIX)


def _record_cache_event(event: str):
    if event == _CACHE_REQUEST_EVENT:
        metrics.counter("jax.compile_cache.requests", _always=True).add(1)
    elif event == _CACHE_HIT_EVENT:
        metrics.counter("jax.compile_cache.hits", _always=True).add(1)


def _record_compile_duration(event: str, duration: float):
    if duration and duration > 0:
        metrics.histogram("jax.compile_secs", _always=True).observe(
            duration)
        # the goodput "compile" bucket: every phase of a compile is
        # time the MXU sat idle (flight_recorder.step_end subtracts
        # this from the train bucket, keeping the fractions disjoint)
        goodput.account("compile", float(duration))


def attach_jax_compile_hook():
    """Best-effort global compile odometer via jax.monitoring events
    (the '/jax/core/compile' family, scoped — cache bookkeeping events
    are excluded). Counts backend compiles into ``jax.compiles_total``
    and feeds per-phase compile durations into ``jax.compile_secs`` +
    the goodput compile fraction. Idempotent; silently unavailable on
    runtimes without jax.monitoring."""
    global _jax_hook_attached
    if _jax_hook_attached:
        return True
    try:
        import jax.monitoring as _mon

        def _listener(event: str, **kw):
            if not _is_compile_event(event):
                _record_cache_event(event)
                return
            metrics.counter("jax.compiles_total", _always=True).add(1)
            # some runtimes ride the duration on the event kwargs
            # instead of the duration channel
            for key in ("duration_secs", "duration_sec", "duration"):
                if key in kw:
                    try:
                        _record_compile_duration(event, float(kw[key]))
                    except (TypeError, ValueError):
                        pass
                    break

        def _dur_listener(event: str, duration: float, **kw):
            if not _is_compile_event(event):
                return
            if event == _BACKEND_COMPILE_EVENT:
                metrics.counter("jax.compiles_total",
                                _always=True).add(1)
            _record_compile_duration(event, duration)

        _mon.register_event_listener(_listener)
        try:
            _mon.register_event_duration_secs_listener(_dur_listener)
        except Exception:
            pass  # count-only on runtimes without the duration channel
        _jax_hook_attached = True
        return True
    except Exception:
        return False
