"""Fleet aggregation: pod-level metric rollups over CPU collectives.

Each host's metrics registry sees only its own process. For pod-level
health (total examples/sec, total collective bytes, did ANY host
recompile) the snapshots must be reduced across hosts. This rides the
same multi-controller runtime the trainers already stand up
(jax.distributed.initialize + jax's gloo CPU collectives): snapshots
are serialized
to JSON, padded to the pod-wide max length, all-gathered through
jax.experimental.multihost_utils (device collectives under the hood —
no side-channel socket protocol to operate), and merged:

  counters    summed (host-count-scaled totals)
  gauges      numeric -> {sum, mean, min, max}; non-numeric -> first
  histograms  count/sum summed, min/max folded, p50/p99 merged as the
              count-weighted mean of host percentiles (approximate —
              exact pod percentiles would need the raw reservoirs)

Single-process runs skip the collectives and return the same shape with
hosts=1, so callers (obs_report, MetricsLogger) are topology-agnostic.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

from . import metrics

__all__ = ["aggregate", "merge_snapshots", "merge_partial"]


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def merge_snapshots(snaps: List[Dict[str, dict]]) -> Dict[str, dict]:
    """Reduce per-host snapshots into one pod rollup (pure function —
    unit-testable without a pod)."""
    out: Dict[str, dict] = {}
    for snap in snaps:
        for key, d in snap.items():
            t = d.get("type")
            cur = out.get(key)
            if cur is None:
                if t == "counter":
                    out[key] = {"type": "counter", "value": d["value"],
                                "hosts": 1}
                elif t == "gauge":
                    v = d["value"]
                    if _num(v):
                        out[key] = {"type": "gauge", "value": v,
                                    "sum": v, "min": v, "max": v,
                                    "hosts": 1}
                    else:
                        out[key] = {"type": "gauge", "value": v,
                                    "hosts": 1}
                else:
                    out[key] = dict(d)
                    out[key]["hosts"] = 1
                continue
            cur["hosts"] += 1
            if t == "counter":
                cur["value"] += d["value"]
            elif t == "gauge":
                v = d["value"]
                if _num(v) and "sum" in cur:
                    cur["sum"] += v
                    cur["min"] = min(cur["min"], v)
                    cur["max"] = max(cur["max"], v)
                    cur["value"] = cur["sum"] / cur["hosts"]
            else:  # histogram
                c_old, c_new = cur.get("count", 0), d.get("count", 0)
                for q in ("p50", "p99"):
                    if q in cur and q in d and (c_old + c_new):
                        cur[q] = ((cur[q] * c_old + d[q] * c_new)
                                  / (c_old + c_new))
                cur["count"] = c_old + c_new
                cur["sum"] = cur.get("sum", 0) + d.get("sum", 0)
                if "min" in d:
                    cur["min"] = min(cur.get("min", d["min"]), d["min"])
                if "max" in d:
                    cur["max"] = max(cur.get("max", d["max"]), d["max"])
    return dict(sorted(out.items()))


def merge_partial(snaps: List[Optional[Dict[str, dict]]]
                  ) -> Dict[str, dict]:
    """Skip-and-flag partial rollup: ``None`` entries — a dead or
    unresponsive source (replica/host) whose snapshot could not be
    fetched — are SKIPPED instead of failing or hanging the merge, and
    the result always carries ``fleet.sources_reporting`` /
    ``fleet.sources_skipped`` gauges so a partial rollup can never
    masquerade as a full one. Callers own the liveness probe (e.g.
    ``ServingFleet.aggregate``'s per-replica snapshot timeout); this
    is the pure merge half."""
    live = [s for s in snaps if s is not None]
    out = merge_snapshots(live)
    hosts = len(live) or 1
    out["fleet.sources_reporting"] = {
        "type": "gauge", "value": len(live), "hosts": hosts}
    out["fleet.sources_skipped"] = {
        "type": "gauge", "value": len(snaps) - len(live),
        "hosts": hosts}
    return out


def _allgather_blobs(data: bytes) -> List[bytes]:
    """All-gather one variable-length byte blob per process via the jax
    device collectives (pad to the pod max, gather lengths alongside)."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils

    lens = multihost_utils.process_allgather(
        np.asarray([len(data)], np.int32))
    lens = np.asarray(lens).reshape(-1)
    max_len = int(lens.max())
    buf = np.zeros((max_len,), np.uint8)
    arr = np.frombuffer(data, np.uint8)
    buf[:arr.size] = arr
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    gathered = gathered.reshape(jax.process_count(), max_len)
    return [gathered[i, :lens[i]].tobytes()
            for i in range(gathered.shape[0])]


def aggregate(snap: Optional[Dict[str, dict]] = None) -> Dict[str, dict]:
    """Pod-level rollup of metric snapshots (this host's registry by
    default). Every host must call this collectively — it is a
    collective operation when process_count > 1."""
    import jax

    if snap is None:
        snap = metrics.snapshot()
    try:
        nproc = jax.process_count()
    except RuntimeError:
        nproc = 1
    if nproc <= 1:
        merged = merge_snapshots([snap])
    else:
        blobs = _allgather_blobs(
            json.dumps(snap, sort_keys=True).encode())
        merged = merge_snapshots([json.loads(b.decode())
                                  for b in blobs])
    merged["fleet.host_count"] = {"type": "gauge", "value": nproc,
                                  "hosts": nproc}
    return merged
