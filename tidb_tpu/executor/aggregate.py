"""Aggregation executors: HashAgg (final/complete) and StreamAgg.

Reference: executor/aggregate.go (HashAggExec, parallel partial/final worker
graph :101-169, serial fallback for distinct :166) and aggfuncs/ (PartialResult
pattern).  The TPU-first shape: the device computes dense *partial* states per
shard (copr/jax_engine segment-reduce); the root HashAgg here only merges
partial-state rows and finalizes — the same partial/final split the reference
uses between coprocessor and root (planner/core/task.go agg pushdown).

Modes:
- partial_input=True  — child streams [group-keys..., partial-states...] rows
  (from cop partial agg); merge + finalize.
- partial_input=False — child streams raw rows; per-chunk partial states are
  computed host-side then merged (distinct aggs force whole-input buffering).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..chunk import Chunk, Column, concat_chunks
from ..copr import aggstate
from ..copr.cpu_engine import _run_agg  # shared host agg kernel
from ..copr.ir import AggregationIR
from ..expr.aggregation import AggDesc
from ..expr.expression import Expression
from .base import ExecContext, Executor


class HashAggExec(Executor):
    def __init__(self, ctx, child: Executor, group_by: List[Expression],
                 aggs: List[AggDesc], partial_input: bool,
                 plan_id: int = -1):
        ftypes = [g.ftype for g in group_by] + [a.ftype for a in aggs]
        super().__init__(ctx, ftypes, [child], plan_id)
        self.group_by = group_by
        self.aggs = aggs
        self.partial_input = partial_input
        self._result: Optional[List[Chunk]] = None
        self._pos = 0

    def _open(self):
        self._result = None
        self._pos = 0
        self._consumed = 0

    def _close(self):
        if getattr(self, "_consumed", 0):
            self.ctx.mem_tracker.release(self._consumed)
            self._consumed = 0
        # a cancel/error between a spill and _spilled_result would
        # otherwise leak the ListInDisk temp files
        lists = getattr(self, "_spill_lists", None)
        if lists is not None:
            for lst in lists:
                lst.close()
            self._spill_lists = None

    N_SPILL_PARTS = 8  # disk partitions when the quota trips

    def _compute(self) -> List[Chunk]:
        n_keys = len(self.group_by)
        # drain with a registered spill hook: over-quota partial chunks
        # partition by key hash to disk and merge per partition
        # (hash_table.go:148-179 / util/memory action.go spill analog)
        self._spill_lists = None
        self._buffered: List[Chunk] = []
        self._consumed = 0
        has_distinct = (not self.partial_input
                        and any(a.distinct for a in self.aggs))
        self._spill_armed = n_keys > 0 and not has_distinct
        if self._spill_armed:
            self.ctx.mem_tracker.register_spill(self._spill)
        # scalar aggregation (no group keys) needs O(1) state: fold each
        # chunk into a one-row partial immediately instead of buffering
        # the whole input (a join's output can dwarf any quota)
        stream_scalar = n_keys == 0 and not has_distinct
        scalar_ir = (AggregationIR(self.group_by, self.aggs, mode="partial")
                     if stream_scalar and not self.partial_input else None)
        scalar_parts: List[Chunk] = []
        while True:
            c = self.child().next()
            if c is None:
                break
            if c.num_rows == 0:
                continue
            if stream_scalar:
                part = c if scalar_ir is None else _run_agg(scalar_ir, c)
                scalar_parts.append(part)
                if len(scalar_parts) >= 64:  # bound the partial list
                    scalar_parts = [concat_chunks(scalar_parts)]
                continue
            self._buffered.append(c)
            nbytes = c.nbytes()
            self._consumed += nbytes
            self.ctx.mem_tracker.consume(nbytes)
        if stream_scalar:
            whole = concat_chunks(scalar_parts)
            if whole is None or whole.num_rows == 0:
                return [aggstate.empty_final_row(self.aggs)]
            final = aggstate.merge_partials_to_final(0, self.aggs, [whole])
            return list(final.split(self.ctx.chunk_size))
        if self._spill_lists is not None:
            # quota tripped during the drain: push the in-memory remainder
            # through the same partitioner so every group lives in exactly
            # one partition, then merge partition-by-partition
            self._spill()
            self._spill_armed = False
            return self._spilled_result(n_keys)
        chunks = self._buffered
        # ownership transfers to the merge below: disarm the hook so a
        # later quota trip elsewhere cannot spuriously re-aggregate data
        # whose result has already been emitted
        self._buffered = []
        self._spill_armed = False
        if self.partial_input:
            final = aggstate.merge_partials_to_final(
                n_keys, self.aggs, chunks)
        else:
            if has_distinct:
                whole = concat_chunks(chunks)
                if whole is None:
                    final = None
                else:
                    ir = AggregationIR(self.group_by, self.aggs, mode="complete")
                    final = _run_agg(ir, whole)
                    if n_keys == 0 and whole.num_rows == 0:
                        final = None
            else:
                # chunk-wise partials computed by a worker pool
                # (aggregate.go:101-169 partial workers; numpy releases the
                # GIL so the pool genuinely overlaps), then one vectorised
                # final merge (a hash-partitioned, pooled merge was slower
                # at every row count measured: PERF.md section 6, PR 37)
                ir = AggregationIR(self.group_by, self.aggs, mode="partial")
                live = [c for c in chunks if c.num_rows > 0]
                par = self.ctx.hashagg_partial_concurrency
                if par > 1 and len(live) > 1:
                    from concurrent.futures import ThreadPoolExecutor

                    from ..metrics import REGISTRY

                    REGISTRY.inc("executor_parallel_workers_total",
                                 min(par, len(live)))
                    with ThreadPoolExecutor(max_workers=par) as pool:
                        partials = list(
                            pool.map(lambda c: _run_agg(ir, c), live)
                        )
                else:
                    partials = [_run_agg(ir, c) for c in live]
                final = aggstate.merge_partials_to_final(
                    n_keys, self.aggs, partials)
        if final is None:
            if n_keys == 0:
                return [aggstate.empty_final_row(self.aggs)]
            return []
        return list(final.split(self.ctx.chunk_size))

    def _spill(self) -> int:
        """Memory-tracker hook: push buffered chunks to hash-partitioned
        disk lists; returns bytes freed."""
        if not self._spill_armed or not self._buffered:
            return 0
        n_keys = len(self.group_by)
        if self.partial_input:
            parts = self._buffered
        else:
            # reduce raw rows to partial states first (much smaller)
            ir = AggregationIR(self.group_by, self.aggs, mode="partial")
            parts = [_run_agg(ir, c) for c in self._buffered]
        if self._spill_lists is None:
            from ..chunk.disk import ListInDisk

            self._spill_lists = [ListInDisk("hashagg")
                                 for _ in range(self.N_SPILL_PARTS)]
        freed = sum(c.nbytes() for c in self._buffered)
        for c in parts:
            h = _partition_hash(c, n_keys)
            if h is None:
                # object keys: single partition (still bounded: disk)
                self._spill_lists[0].add(c)
                continue
            for p in range(self.N_SPILL_PARTS):
                sel = h % self.N_SPILL_PARTS == p
                if sel.any():
                    self._spill_lists[p].add(c.filter(sel))
        self._buffered.clear()
        self.ctx.mem_tracker.release(freed)
        self._consumed = max(self._consumed - freed, 0)
        from ..metrics import REGISTRY

        REGISTRY.inc("hashagg_spills_total")
        return freed

    def _spilled_result(self, n_keys: int):
        """Merge each disk partition separately — peak memory is bounded by
        the largest partition, not the whole input."""
        out: List[Chunk] = []
        for lst in self._spill_lists:
            part_chunks = list(lst)
            lst.close()
            merged = aggstate.merge_partials_to_final(
                n_keys, self.aggs, part_chunks)
            if merged is not None:
                out.extend(merged.split(self.ctx.chunk_size))
        self._spill_lists = None
        return out

    def _next(self) -> Optional[Chunk]:
        if self._result is None:
            self._result = self._compute()
        if self._pos >= len(self._result):
            return None
        c = self._result[self._pos]
        self._pos += 1
        return c


class StreamAggExec(Executor):
    """Aggregation over input sorted by group keys: bounded state (only the
    open group's accumulator is live between chunks).

    Reference: executor/aggregate.go StreamAggExec."""

    def __init__(self, ctx, child: Executor, group_by: List[Expression],
                 aggs: List[AggDesc], partial_input: bool = False,
                 plan_id: int = -1):
        ftypes = [g.ftype for g in group_by] + [a.ftype for a in aggs]
        super().__init__(ctx, ftypes, [child], plan_id)
        self.group_by = group_by
        self.aggs = aggs
        self.partial_input = partial_input
        self._open_partial: Optional[Chunk] = None  # pending group rows
        self._done = False

    def _open(self):
        self._open_partial = None
        self._done = False

    def _next(self) -> Optional[Chunk]:
        if self._done:
            return None
        n_keys = len(self.group_by)
        while True:
            c = self.child().next()
            if c is None:
                self._done = True
                if self._open_partial is not None:
                    out = aggstate.merge_partials_to_final(
                        n_keys, self.aggs, [self._open_partial]
                    )
                    self._open_partial = None
                    return out
                if n_keys == 0:
                    return aggstate.empty_final_row(self.aggs)
                return None
            if c.num_rows == 0:
                continue
            if self.partial_input:
                part = c
            else:
                ir = AggregationIR(self.group_by, self.aggs, mode="partial")
                part = _run_agg(ir, c)
            if self._open_partial is not None:
                part = self._open_partial.append(part)
            if part.num_rows <= 1 or n_keys == 0:
                self._open_partial = part
                continue
            # emit all fully-closed groups; hold back the last (still open)
            gidx = aggstate.group_indices(
                [part.col(i) for i in range(n_keys)])[0]
            closed_mask = gidx != gidx[-1]
            closed = part.filter(closed_mask)
            self._open_partial = part.filter(~closed_mask)
            if closed.num_rows:
                return aggstate.merge_partials_to_final(
                    n_keys, self.aggs, [closed]
                )


def _partition_hash(c: Chunk, n_keys: int):
    """Vectorized per-row hash over the key columns; None when a key column
    holds host objects (strings) — those merges stay serial."""
    h = np.zeros(c.num_rows, dtype=np.uint64)
    for i in range(n_keys):
        col = c.col(i)
        data = col.data
        if data.dtype == object:
            return None
        if np.issubdtype(data.dtype, np.floating):
            # bit view (with -0.0 folded) so fractional keys spread across
            # partitions — value truncation would collapse [0,1) to one
            # worker (same canonicalization as aggstate.group_indices)
            v = np.where(data == 0.0, 0.0, data).astype(
                np.float64).view(np.uint64)
        else:
            v = data.astype(np.int64, copy=False).view(np.uint64)
        if col.valid is not None:
            # what lies under a NULL is not part of the key
            v = np.where(col.valid, v, np.uint64(0))
        v = v * np.uint64(0x9E3779B97F4A7C15)
        h = (h * np.uint64(31)) ^ (v >> np.uint64(7)) ^ v
        h = h ^ (~col.validity()).astype(np.uint64)
    return h
