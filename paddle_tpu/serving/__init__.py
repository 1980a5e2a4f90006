"""paddle_tpu.serving — the continuous-batching production inference
path (ROADMAP item 1, the "millions of users" gap).

The reference ships inference as a first-class measured stack
(paddle/fluid/inference/); our Predictor covers the per-call artifact
surface, but LM serving needs an *engine*: mixed-length request
streams, admission into a running decode, and memory that outlives one
call. TPU-native shape (the TVM lesson — fixed executables + buckets
beat dynamic shapes):

  paged_cache  fixed pool of [n_blocks, block_size, n_heads * hd] KV
               pages per layer + host block tables; eviction = a host
               list splice; page refcounts + a radix prefix index give
               copy-on-write prompt sharing (prefix_sharing=True)
  state_cache  for a decoder of retention layers: one fixed-size f32
               state ROW a request per layer in place of pages and
               tables; row 0 is the scratch row of dead lanes
  programs     THREE compiled programs (bucketed prefill, paged decode
               step, and the mid-stream chunk forward that serves both
               speculative verify and shared-prefix suffix prefill)
               with donated pools; steady state runs exactly the
               engine's expected_executables, RecompileSentinel-pinned
  scheduler    FIFO continuous batching: admit/retire at token
               boundaries, whole-lifetime page reservation
  engine       ServingEngine: bf16 decode default, f32 parity mode
               bit-for-bit vs models/generation.py greedy; raw-speed
               levers — quant="int8" PTQ decode, speculative_k draft/
               verify (accepted tokens bit-identical to greedy), and
               radix/COW prefix page sharing
  loadgen      open-loop trace replay + SLO stats (tools/serving_bench)
  fleet        ServingFleet: the SLO-aware self-healing control loop —
               supervisor-driven autoscale, exact requeue of a dead
               replica's in-flight requests, hot weight swaps, priority
               classes with overload shedding, chaos-drill receipts
               (tools/serving_chaos_drill.py)

Multi-replica serving runs through the fleet; per-replica snapshots
roll up skip-and-flag (a dead replica can't hang the gather) and the
shared serving.* metrics ride observability.fleet.aggregate() like
every other subsystem.

Request anatomy (observability.reqtrace, DESIGN.md "Request
anatomy"): scheduler/engine/fleet emit per-request spans at the token
boundaries they own (class-queue wait, admission, prefill bucket,
decode chunk with replica+tick, requeue hop, swap-flip pause) behind
one module bool; `explain_tail` attributes a p99-cohort request's
latency to components summing to ~1.0, the SLO error-budget BurnMeter
feeds `decide_scale(burn_alert=)`, and
tpu_doctor.serving_breach_verdict names a breach's cause from the
trace alone.
"""
from .engine import ServingConfig, ServingEngine, \
    build_serving_snapshot
from .fleet import (FleetConfig, FleetRequest, PRIORITY_CLASSES,
                    Replica, ServingFleet, ServingSLO)
from .paged_cache import PagedKVCache
from .state_cache import StateCache
from .scheduler import BucketLadder, FifoScheduler, Request
from . import loadgen

__all__ = ["ServingConfig", "ServingEngine", "PagedKVCache", "StateCache",
           "BucketLadder", "FifoScheduler", "Request", "loadgen",
           "ServingFleet", "ServingSLO", "FleetConfig", "FleetRequest",
           "Replica", "PRIORITY_CLASSES", "build_serving_snapshot"]
