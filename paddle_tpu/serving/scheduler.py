"""Continuous-batching scheduler: FIFO admission into running decode
steps, retirement at token boundaries.

The host-side half of the serving engine. State machine per request:

  QUEUED --admit (slots + pages available)--> RUNNING
  RUNNING --max_new reached | eos emitted--> FINISHED (pages freed)

Admission happens between decode steps ("in-flight": the running batch
is never drained to let newcomers in), strictly FIFO — the head of the
queue blocks admission when it doesn't fit, rather than letting small
requests starve a big one. Page accounting is whole-lifetime at
admission (see paged_cache), so admission control is the single
backpressure point and a running request can never OOM.

The bucket ladder quantizes dynamic shapes into the fixed executable
set (the dynamic-shape bucketing policy applied to serving): the
admit batch's prompts, laid end to end, pad to the narrowest packed
width that holds them (or, a row a prompt, to the smallest prefill
bucket that fits the LONGEST prompt in the batch), decode runs at the
smallest slot-count bucket covering the active set. Executable count
is therefore bounded by ladder size, not by the length mix of the
traffic.

Tensor parallelism changes NOTHING here — that is a load-bearing
contract, not an accident. The scheduler's decisions are over
requests, slots, pages and positions, never heads, and under a
``ServingConfig(plan=MeshPlan(tp=N))`` engine the block tables and
every queue stay host-replicated while only the device pools shard
over heads. ONE host decision stream drives all tp chips; anything
added here that branches on a per-chip quantity would fork that
stream and break the shard_map programs' replicated-operand contract.
"""
from __future__ import annotations

import itertools
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from ..observability import reqtrace as _rt

__all__ = ["Request", "BucketLadder", "FifoScheduler"]

_rid_counter = itertools.count()

# the packed prefill's widths are whole units of at most this many rows:
# the prefill attention kernel's query block, so that a width keeps it
_PACKED_UNIT = 512


@dataclass
class Request:
    """One generation request plus its runtime state."""
    ids: np.ndarray                    # 1-D int32 true prompt
    max_new_tokens: int
    rid: object = None
    eos_token_id: Optional[int] = None
    arrival: Optional[float] = None    # perf_counter() timestamp
    # -- runtime (engine-owned) ---------------------------------------------
    pos: int = 0                       # next K/V write position
    out: List[int] = field(default_factory=list)
    shared_tokens: int = 0             # prompt head served from shared
    #                                    pages (prefix-sharing admission)
    submit_ts: Optional[float] = None  # engine-queue entry (reqtrace)
    admitted_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    done_ts: Optional[float] = None
    finish_reason: Optional[str] = None

    def __post_init__(self):
        self.ids = np.asarray(self.ids, np.int32).reshape(-1)
        if self.ids.size < 1:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={self.max_new_tokens} must be >= 1")
        if self.rid is None:
            self.rid = next(_rid_counter)

    @property
    def prompt_len(self) -> int:
        return int(self.ids.size)

    @property
    def total_tokens(self) -> int:
        return self.prompt_len + int(self.max_new_tokens)

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    def accept(self, tok: int):
        """Record one emitted token; flip to FINISHED on budget or
        eos. Engine calls this once per token boundary."""
        self.out.append(int(tok))
        if (self.eos_token_id is not None
                and int(tok) == int(self.eos_token_id)):
            self.finish_reason = "eos"
        elif len(self.out) >= self.max_new_tokens:
            self.finish_reason = "length"


class BucketLadder:
    """The fixed shape ladder: prefill widths (multiples of
    block_size, ascending), the packed prefill's row widths, and decode
    slot-count buckets.

    A packed prefill lays the admit batch's prompts end to end in one
    row, each from a page boundary. Its widths are derived from the
    buckets and the admit width `max_admit`, and are as many as the
    buckets: the narrowest holds `max_admit` prompts of the smallest
    bucket (what the smallest per-row shape computes, so a lone short
    prompt costs no more than it did), the widest `max_admit` prompts of
    the largest (every admit batch the buckets take fits), and those
    between are spaced evenly. Each is rounded up to whole units of
    the narrowest's largest power-of-two part no wider than 512 rows (the
    prefill attention's query block; and so to whole pages). GPT-2's
    4 admits over buckets 128-1,024 give 512, 2,048, 3,072, 4,096;
    2 admits over 2,048-5,120 give 4,096, 6,144, 8,192, 10,240."""

    def __init__(self, prefill: Sequence[int], decode: Sequence[int],
                 block_size: int, max_admit: int = 1):
        self.prefill = tuple(sorted(int(b) for b in prefill))
        self.decode = tuple(sorted(int(b) for b in decode))
        if not self.prefill or not self.decode:
            raise ValueError("empty bucket ladder")
        for b in self.prefill:
            if b < 1 or b % block_size:
                raise ValueError(
                    f"prefill bucket {b} must be a positive multiple "
                    f"of block_size {block_size}")
        if any(b < 1 for b in self.decode):
            raise ValueError(f"decode buckets {self.decode} must be "
                             ">= 1")
        narrow = max_admit * self.prefill[0]
        wide = max_admit * self.prefill[-1]
        unit = math.lcm(math.gcd(narrow, _PACKED_UNIT), int(block_size))
        n = len(self.prefill)
        self.packed = tuple(sorted({
            -(-(narrow + (wide - narrow) * i // max(n - 1, 1)) // unit)
            * unit for i in range(n)}))

    def pick_prefill(self, length: int) -> int:
        for b in self.prefill:
            if b >= length:
                return b
        raise ValueError(
            f"prompt length {length} exceeds the largest prefill "
            f"bucket {self.prefill[-1]}")

    def pick_packed(self, tokens: int) -> int:
        """The narrowest packed width that holds `tokens` rows."""
        for w in self.packed:
            if w >= tokens:
                return w
        raise ValueError(
            f"{tokens} packed rows exceed the widest packed prefill "
            f"{self.packed[-1]}")

    def pick_decode(self, n_active: int) -> int:
        for b in self.decode:
            if b >= n_active:
                return b
        raise ValueError(
            f"{n_active} active slots exceed the largest decode "
            f"bucket {self.decode[-1]}")

    @property
    def size(self) -> int:
        """Total executable budget: the steady-state compile count the
        sentinel holds the engine to."""
        return len(self.prefill) + len(self.decode)


class FifoScheduler:
    """Queue + running set with strict-FIFO admission."""

    def __init__(self, max_slots: int, max_admit: int):
        if max_admit < 1 or max_slots < 1:
            raise ValueError("max_slots and max_admit must be >= 1")
        if max_admit > max_slots:
            raise ValueError(
                f"max_admit={max_admit} > max_slots={max_slots}")
        self.max_slots = int(max_slots)
        self.max_admit = int(max_admit)
        self.queue: deque = deque()
        self.running: dict = {}

    def submit(self, req: Request):
        if _rt._enabled:
            req.submit_ts = time.perf_counter()
        self.queue.append(req)
        return req.rid

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def n_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.queue or self.running)

    def take_admissible(self, cache, extra_caches=()) -> List[Request]:
        """Pop the FIFO prefix that fits this token boundary: bounded
        by free slots, the admit width, and page availability
        (whole-lifetime pages per request, accounted cumulatively
        across the batch). Stops at the first request that does NOT
        fit — no overtaking, no starvation.

        ``extra_caches`` (the speculative draft model's page pool)
        must fit every admitted request too — the draft cache tracks
        the target position-for-position, so a request admitted into
        one but not the other would wedge mid-decode. Availability
        counts reclaimable prefix-index pages (``available_pages``):
        admission may promise pages the radix index can give back.
        The count is conservative under sharing — a prefix hit at
        alloc time needs fewer fresh pages than budgeted here."""
        caches = (cache,) + tuple(extra_caches)
        admitted: List[Request] = []
        spoken_for = [0] * len(caches)
        while (self.queue
               and len(admitted) < self.max_admit
               and self.n_running + len(admitted) < self.max_slots):
            head = self.queue[0]
            if any(taken + c.blocks_for(head.total_tokens)
                   > c.available_pages
                   for taken, c in zip(spoken_for, caches)):
                break
            for i, c in enumerate(caches):
                spoken_for[i] += c.blocks_for(head.total_tokens)
            admitted.append(self.queue.popleft())
        for r in admitted:
            self.running[r.rid] = r
        if _rt._enabled and admitted:
            now = time.perf_counter()
            for r in admitted:
                _rt.record_span(
                    r.rid, "admission",
                    now if r.submit_ts is None else r.submit_ts, now)
        return admitted

    def retire_finished(self) -> List[Request]:
        done = [r for r in self.running.values() if r.done]
        for r in done:
            del self.running[r.rid]
        return done

    def active(self) -> List[Request]:
        return [r for r in self.running.values() if not r.done]
