"""The stream re-rank kernel's share of its HBM roofline: the f32 rows it
refines plus the query rows (``bench/roofline.py``) over 819 GB/s, over the
summed device time of the kernel's operations in the trace."""
from bench import roofline

NAME = "rerank_roofline"
UNIT = "%"
LAYER = "re-rank kernel (kernels/rerank_kernel.py)"
MOVES = "p99_ms"
SOURCE = "device_trace"
KERNELS = ("rerank_stream_topk",)


def read(run):
    return roofline.share(roofline.rerank_bytes(run.sums, run.config),
                          run.kernel_seconds(KERNELS), run.peaks)
