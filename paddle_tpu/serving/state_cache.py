"""Recurrent-state cache: one fixed-size state ROW a request.

A retention layer (models/decoder.py, "the retention mixer") keeps of
a request's whole past one state per layer and key-value head, whose
size does not grow with the tokens held. So where the paged cache has
pages, a block table a request and a free list of pages, this cache has

- device side: per layer ``S [rows, n_kv, F, head, head]`` and
  ``z [rows, n_kv, F rounded up to 8, head]`` in float32 (F = head/2 +
  1 circular offsets: the symmetric second tensor power of a key, in
  whole tiles; z's padding rows stay zero and keep its default device
  layout row-major, decoder.py), allocated once, donated through every
  compiled program and written in place, as the page pools are;
- host side: a free list of rows and ``request -> row``. Row 0 is
  SCRATCH, as page 0 is: never allocated; padded admit lanes and dead
  decode lanes address it, so an inactive lane needs no condition.

A row needs no zeroing dispatch: the prefill program WRITES the whole
row (the state as of the prompt's last true token), so a row freed and
given to the next request starts from that request's prompt alone.

The engine and the scheduler reach a cache through one surface, which
this class gives with a row as the unit: ``pools``, ``alloc``/``free``,
``blocks_for`` (always 1: a request takes one row whatever its length),
``available_pages``/``n_free``/``n_live``/``n_blocks``,
``table_array`` (the program feed: here one row id a lane) and
``span_counts`` (what the ``step`` span says of the cache). What a
paged cache has beyond that (prefix sharing, copy-on-write) has no
counterpart here: a state cannot be cut at a prefix, so sharing would
need snapshots of the state at page boundaries, which nothing takes
yet, and the engine refuses the option for such a model.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

__all__ = ["StateCache"]


class StateCache:
    """State rows + the host-side row allocator. ``pools`` is the
    device pytree (a tuple over layers of ``(S, z)``) the compiled
    programs consume and return; the engine swaps the attribute after
    every donated call."""

    def __init__(self, n_layers: int, n_rows: int, n_kv_heads: int,
                 head_dim: int, dtype="float32"):
        if n_rows < 2:
            raise ValueError(
                f"n_rows={n_rows}: need at least 1 allocatable row "
                "beyond the reserved scratch row 0")
        import jax.numpy as jnp
        self.n_layers = int(n_layers)
        self.n_blocks = int(n_rows)         # rows, scratch included
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        feats = self.head_dim // 2 + 1
        heads = (self.n_blocks, self.n_kv_heads)
        self.pools = tuple(
            (jnp.zeros(heads + (feats, self.head_dim, self.head_dim),
                       self.dtype),
             jnp.zeros(heads + (-(-feats // 8) * 8, self.head_dim),
                       self.dtype))
            for _ in range(self.n_layers))
        # LIFO, as the page free list: a freed row goes to the next
        # admission
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._rows: Dict[object, int] = {}

    # -- sizing --------------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Rows a request of any length takes."""
        return 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        """Rows held by requests; ``1 + n_free + n_live == n_blocks``."""
        return len(self._rows)

    @property
    def available_pages(self) -> int:
        """What admission may promise: the free rows."""
        return len(self._free)

    # -- alloc / free --------------------------------------------------------
    def alloc(self, req_id, n_tokens: int = 0) -> int:
        if req_id in self._rows:
            raise ValueError(f"request {req_id!r} already holds a row")
        if not self._free:
            raise MemoryError(
                f"StateCache: no free row for request {req_id!r} "
                f"({self.n_live} live of {self.n_blocks - 1})")
        row = self._free.pop()
        self._rows[req_id] = row
        return row

    def free(self, req_id) -> int:
        row = self._rows.pop(req_id)
        self._free.append(row)
        return row

    def copy_executables(self) -> int:
        """Programs of the cache's own (a paged cache's copy-on-write
        copy): none."""
        return 0

    def span_counts(self) -> dict:
        """What the engine writes of this cache on its ``step`` span."""
        return {"state_rows_live": self.n_live}

    # -- program feed --------------------------------------------------------
    def table_array(self, req_ids: Sequence, width: int = 0) -> np.ndarray:
        """``[len(req_ids)]`` int32 row ids for the compiled programs;
        a lane without a request (None) addresses the scratch row."""
        return np.asarray([0 if rid is None else self._rows[rid]
                           for rid in req_ids], np.int32)

    def check_invariants(self):
        rows = sorted(self._rows.values())
        assert len(set(rows)) == len(rows), "a row held twice"
        assert 0 not in rows and 0 not in self._free, "scratch handed out"
        assert sorted(rows + self._free) == list(
            range(1, self.n_blocks)), "rows lost or made up"
