"""The measured window: back-to-back multiply calls, counted batch by batch.

The window opens when the first call starts. The consumer copies each batch
to host memory, as a caller who wants C does, and records its arrival. At
the first arrival at or after ``seconds`` it stops the call by raising
``StopWindow``; the window ends at that arrival. A call that finishes first
is followed by the next one on the same operands (a closed loop with one
caller). Work the driver had queued beyond the last counted batch is not
counted and its time is not in the window.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

from .check import HostBatch


class StopWindow(Exception):
    """Raised by the consumer to end the multiply call at the window's end."""


def copy_to_host(c_batch):
    """The batch's tile arrays in host memory (blocks until they are)."""
    import jax

    rows, cols, vals, nnz = jax.device_get(
        (c_batch.rows, c_batch.cols, c_batch.vals, c_batch.nnz))
    return (np.asarray(rows), np.asarray(cols), np.asarray(vals),
            np.asarray(nnz), int(c_batch.tile_shape[0]))


class Window:
    """Counts batches as they reach the consumer of ``multiply``.

    ``multiply(consumer)`` makes one call of the system under test, handing
    each batch to ``consumer(batch_index, c_batch, col_map)``. ``clock`` and
    ``copy`` are injectable so the stop logic can be driven without a
    device."""

    def __init__(self, seconds: float, clock: Callable[[], float] = None,
                 copy: Callable = copy_to_host, span=None):
        self.seconds = float(seconds)
        self.clock = clock or time.perf_counter
        self.copy = copy
        self.span = span  # context-manager factory for host spans, or None
        self.batches: list = []
        self.calls = 0
        self.start = None
        self.end = None

    def _span(self, name):
        if self.span is None:
            import contextlib

            return contextlib.nullcontext()
        return self.span(name)

    def consumer(self, bi, c_batch, col_map):
        with self._span("bench.consumer"):
            rows, cols, vals, nnz, tile_rows = self.copy(c_batch)
            t = self.clock()
            self.batches.append(HostBatch(
                call=self.calls - 1, index=int(bi), arrival=t,
                col_map=np.asarray(col_map), rows=rows, cols=cols, vals=vals,
                nnz=nnz, tile_rows=tile_rows,
            ))
        if t - self.start >= self.seconds:
            self.end = t
            raise StopWindow
        return int(nnz.sum())

    def run(self, multiply: Callable) -> "Window":
        self.start = self.clock()
        while self.end is None:
            self.calls += 1
            try:
                with self._span("bench.call"):
                    multiply(self.consumer)
            except StopWindow:
                break
        return self

    @property
    def elapsed(self) -> float:
        return self.end - self.start

    def arrivals(self) -> list:
        """Seconds from the window's start to each counted arrival."""
        return [b.arrival - self.start for b in self.batches]
