"""Throughput / MFU reporter: examples/sec against XLA's own FLOP count.

MFU (model FLOPs utilization) = achieved model FLOP/s over the chip's
peak FLOP/s. The numerator's FLOPs-per-step comes from
``cost_analysis()`` of the LOWERED train executable — the compiler's
count of the program actually run (remat recompute included), not a
hand-derived 6ND guess. The denominator is the per-chip peak from the
public TPU specs table (override: PD_PEAK_FLOPS), times the device
count the executable spans.

``ThroughputMeter`` is the per-step accumulator engines/callbacks feed;
it publishes ``throughput.examples_per_sec``, ``throughput.mfu`` (on an
accelerator only — the CPU has no peak, so no MFU) and
``throughput.model_flops_per_step`` gauges plus an
``examples_total`` counter through the metrics registry.
"""
from __future__ import annotations

import os
import time
from typing import Optional

from . import metrics

__all__ = ["chip_peak_flops", "flops_of_compiled", "step_flops",
           "ThroughputMeter", "PEAK_FLOPS_BY_KIND"]

# bf16 peak FLOP/s per chip by TPU generation (public cloud specs;
# "TPU v5 lite" is what a v5e reports). PD_PEAK_FLOPS overrides for
# unlisted hardware. ONE copy of the hardware truth.
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def chip_peak_flops(device=None) -> Optional[float]:
    """Peak FLOP/s for one device: PD_PEAK_FLOPS > spec table. The
    CPU has no peak here — None, and callers report no MFU: a
    utilization is a device number. An accelerator whose device_kind
    the table cannot name is an error, never a guess — an MFU built on
    a guessed denominator is off by up to 3.3x across the table."""
    env = os.environ.get("PD_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    for k, v in PEAK_FLOPS_BY_KIND.items():
        if kind.lower().startswith(k.lower()):
            return v
    if getattr(device, "platform", "") == "cpu":
        return None
    raise ValueError(
        f"chip_peak_flops: device_kind {kind!r} is not in "
        "PEAK_FLOPS_BY_KIND — add its published per-chip peak there "
        "(or pin PD_PEAK_FLOPS=<FLOP/s>)")


def flops_of_compiled(compiled) -> float:
    """Total FLOPs from a compiled executable's cost analysis (sums the
    per-module dicts newer jax returns as a list)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return -1.0
    if ca is None:
        return -1.0
    if isinstance(ca, dict):
        ca = [ca]
    total = 0.0
    for mod in ca:
        total += float(mod.get("flops", 0.0))
    return total if total > 0 else -1.0


def step_flops(fn, *args, **kwargs) -> float:
    """FLOPs per call of `fn(*args)` via lower().compile() cost
    analysis. `fn` may be a jax.jit function or a plain traceable
    callable (wrapped in jit here). AOT lowering does not touch the
    function's executable cache — safe to use next to the recompile
    sentinel."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return flops_of_compiled(jitted.lower(*args, **kwargs).compile())


class ThroughputMeter:
    """Per-step examples/sec + MFU accumulator.

        meter = ThroughputMeter(examples_per_step=batch,
                                flops_per_step=step_flops(step, *args))
        for _ in range(n):
            t0 = time.perf_counter()
            train_step(...)
            meter.step(time.perf_counter() - t0)
        meter.report()   # {'examples_per_sec':..., 'mfu':...}
    """

    def __init__(self, examples_per_step: int,
                 flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 n_devices: Optional[int] = None,
                 name: str = "train"):
        self.examples_per_step = int(examples_per_step)
        self.flops_per_step = flops_per_step
        self.name = name
        if peak_flops is None or n_devices is None:
            import jax
            devs = jax.devices()
            if n_devices is None:
                n_devices = len(devs)
            if peak_flops is None:
                peak_flops = chip_peak_flops(devs[0])
        # None on the CPU: examples/sec still reports, MFU does not
        self.peak_flops_total = (None if peak_flops is None
                                 else float(peak_flops) * int(n_devices))
        self.n_devices = int(n_devices)
        self._steps_s = []
        self._t_last = None

    # -- feeding -------------------------------------------------------------
    def step(self, seconds: Optional[float] = None):
        """Record one train step. Pass the measured wall seconds, or
        call with no argument to use the gap since the previous call."""
        now = time.perf_counter()
        if seconds is None:
            seconds = (now - self._t_last) if self._t_last is not None \
                else None
        self._t_last = now
        if seconds is None or seconds <= 0:
            return self
        self._steps_s.append(float(seconds))
        # per-step path: gate before the instrument name/label work
        # (the repo_lint obs-gate rule; the registry would no-op the
        # disabled write anyway)
        if metrics._enabled:
            metrics.counter("throughput.examples_total").add(
                self.examples_per_step)
            metrics.histogram(f"{self.name}.step_ms").observe(
                seconds * 1e3)
        return self

    # -- reporting -----------------------------------------------------------
    def _median_step(self) -> float:
        if not self._steps_s:
            return -1.0
        ys = sorted(self._steps_s)
        return ys[len(ys) // 2]

    def examples_per_sec(self) -> float:
        med = self._median_step()
        return self.examples_per_step / med if med > 0 else -1.0

    def mfu(self) -> Optional[float]:
        """None where there is no peak to divide by (the CPU)."""
        if self.peak_flops_total is None:
            return None
        med = self._median_step()
        if med <= 0 or not self.flops_per_step \
                or self.flops_per_step <= 0:
            return -1.0
        return (self.flops_per_step / med) / self.peak_flops_total

    def report(self) -> dict:
        """Publish gauges and return the rollup dict."""
        eps = self.examples_per_sec()
        mfu = self.mfu()
        if mfu is not None:
            mfu = round(mfu, 6)
            metrics.gauge("throughput.mfu").set(mfu)
        metrics.gauge("throughput.examples_per_sec").set(round(eps, 3))
        if self.flops_per_step and self.flops_per_step > 0:
            metrics.gauge("throughput.model_flops_per_step").set(
                float(self.flops_per_step))
        return {
            "examples_per_sec": round(eps, 3),
            "mfu": mfu,
            "model_flops_per_step": self.flops_per_step,
            "peak_flops_total": self.peak_flops_total,
            "n_devices": self.n_devices,
            "steps": len(self._steps_s),
        }
