"""Double-buffered manual DMA pipeline for the streaming re-rank kernel.

The re-rank kernel (``kernels/rerank_kernel.py``) runs a sequence of grid
steps, each of which DMAs its candidate rows of the in-place HBM base into
VMEM scratch and then computes on them. (The stream *scan* kernels fetch
their code tiles through scalar-prefetch-indexed BlockSpecs instead, which
Pallas double-buffers itself; see ``fastscan_kernel._codes_spec``.)
Issuing the copy and immediately waiting on it (one DMA per step) leaves
the DMA engine idle during compute and the compute units idle during the
copy. The classic fix is a two-slot
pipeline: while step ``t`` computes out of scratch slot ``t % 2``, step
``t+1``'s copy is already in flight into slot ``(t+1) % 2`` — two scratch
buffers, two DMA semaphores, copy latency hidden behind compute.

``double_buffered_dma`` is that pipeline as a step-local helper: kernels call
it once per sequential step with callbacks that start/wait the step's
transfer(s), and it schedules

    step 0:  start(0) ; start(1) ; wait(0) ; <compute on slot 0>
    step t:  start(t+1)          ; wait(t) ; <compute on slot t % 2>

Correctness of the slot rotation relies only on steps executing in order
(TPU grid dims are sequential unless declared parallel; ``fori_loop`` bodies
trivially so) and on the caller computing on slot ``t % 2`` after the call:
slot ``(t+1) % 2`` was last read by step ``t-1``, whose compute finished
before step ``t`` began, so overwriting it is race-free.

Interpret mode executes copies synchronously, so the pipeline degenerates to
the one-DMA-per-step schedule with identical results — bit-identity is
asserted in ``tests/test_stream_rerank.py``.
"""
from __future__ import annotations

from jax.experimental import pallas as pl


def double_buffered_dma(step, total: int, start, wait) -> None:
    """Run one step of a two-slot DMA pipeline over ``total`` sequential steps.

    step:  traced i32 — this step's position in the global sequential order
           (for a 2-D grid: ``gi * n_inner + ni``).
    total: static int — number of steps in the sequence.
    start: ``start(s, slot)`` issues the copy/copies for step ``s`` into
           scratch slot ``slot`` (0 or 1). Called under ``pl.when``, at most
           once per step across the whole pipeline.
    wait:  ``wait(s, slot)`` blocks until step ``s``'s copy/copies into
           ``slot`` have landed. Must mirror ``start`` transfer-for-transfer
           (each DMA wait consumes exactly one start's semaphore signals).

    After this returns, step ``step``'s data is resident in slot
    ``step % 2`` and step ``step + 1``'s transfer is in flight.
    """
    nxt = step + 1

    @pl.when(step == 0)
    def _prime():  # first step of the sequence: nothing is in flight yet
        start(step, 0)

    @pl.when(nxt < total)
    def _prefetch():  # overlap the next tile's copy with this tile's compute
        start(nxt, nxt % 2)

    wait(step, step % 2)

