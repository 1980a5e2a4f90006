"""Typed runtime flag registry with FLAGS_* environment bridge.

TPU-native equivalent of the reference's gflags machinery
(/root/reference/paddle/fluid/platform/flags.cc:33-539 and
pybind/global_value_getter_setter.cc): a typed, documented registry whose
values can be set from the environment (``FLAGS_<name>``) at import time and
read/written at runtime via ``get_flags``/``set_flags``.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

_LOCK = threading.RLock()


class _Flag:
    __slots__ = ("name", "type", "default", "value", "help", "validator")

    def __init__(self, name, type_, default, help_, validator=None):
        self.name = name
        self.type = type_
        self.default = default
        self.value = default
        self.help = help_
        self.validator = validator


_REGISTRY: Dict[str, _Flag] = {}


def _parse(type_, raw: str):
    if type_ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return type_(raw)


def define_flag(name: str, default: Any, help: str = "",
                type: Optional[type] = None,
                validator: Optional[Callable[[Any], bool]] = None):
    """Register a flag. Environment variable FLAGS_<name> overrides default."""
    t = type or (bool if isinstance(default, bool) else builtins_type(default))
    with _LOCK:
        if name in _REGISTRY:
            raise ValueError(f"flag '{name}' already defined")
        flag = _Flag(name, t, default, help, validator)
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            flag.value = _parse(t, env)
        _REGISTRY[name] = flag
    return flag


def builtins_type(v):
    return bool if isinstance(v, bool) else v.__class__


def set_flags(flags: Dict[str, Any]):
    """Set one or more flags at runtime (paddle.set_flags equivalent)."""
    with _LOCK:
        for k, v in flags.items():
            k = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
            if k not in _REGISTRY:
                raise KeyError(f"unknown flag '{k}'")
            f = _REGISTRY[k]
            if isinstance(v, str) and f.type is not str:
                v = _parse(f.type, v)
            if f.validator is not None and not f.validator(v):
                raise ValueError(f"invalid value {v!r} for flag '{k}'")
            f.value = f.type(v) if f.type is not bool else bool(v)


def get_flags(flags=None) -> Dict[str, Any]:
    """Read flags. `flags` may be a name, list of names, or None for all."""
    with _LOCK:
        if flags is None:
            names = list(_REGISTRY)
        elif isinstance(flags, str):
            names = [flags]
        else:
            names = list(flags)
        out = {}
        for k in names:
            k2 = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
            if k2 not in _REGISTRY:
                raise KeyError(f"unknown flag '{k}'")
            out[k] = _REGISTRY[k2].value
        return out


def flag_value(name: str):
    return _REGISTRY[name].value


# ---------------------------------------------------------------------------
# Core flags (subset of reference platform/flags.cc relevant to a TPU build)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf after each eager op (debug).")
define_flag("eager_op_jit", True,
            "Use a per-op jit cache for eager execution (lower dispatch "
            "overhead; compiled path is the real perf story).")
define_flag("benchmark", False, "Record per-op timing stats in eager mode.")
define_flag("op_stats", False,
            "Count per-op eager dispatches in the stat monitor "
            "(platform/monitor.h analogue).")
define_flag("seed", 0, "Global RNG seed (0 = nondeterministic).")
define_flag("allocator_strategy", "xla",
            "Memory strategy. XLA owns device memory on TPU; this flag exists "
            "for capability parity and host-side pools.")
define_flag("tpu_matmul_precision", "default",
            "jax.lax matmul precision: default|high|highest.")
define_flag("use_bf16_compute", True,
            "Prefer bfloat16 compute in AMP lists (TPU MXU native).")
define_flag("log_level", 0, "Verbosity (glog VLOG analogue).")


def apply_compile_cache() -> str:
    """Place jax's persistent compilation cache, by ONE rule, and
    return the directory in use. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set jax already has it, and no ``jax_compilation_cache_dir`` update
    is made here at all; otherwise the cache is ``<checkout>/.jax_cache``
    — a fixed path (never a temporary, pid- or time-derived one), since
    the next run only finds what this one compiled if it looks in the
    same place. Entry points (chip_smoke.py, bench.py, the examples)
    call it before their first compile; cache hits are observable
    through the sentinel's jax.monitoring listener
    (``jax.compile_cache.requests`` / ``jax.compile_cache.hits``)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
