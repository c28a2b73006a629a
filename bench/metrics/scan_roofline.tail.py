"""The stream scan kernel's share of its HBM roofline in an open-loop cell:
the bytes its queries need (``bench/roofline.py``) over 819 GB/s, over the
summed device time of the kernel's operations in the trace."""
from bench import roofline

NAME = "scan_roofline.tail"
UNIT = "%"
LAYER = "scan kernel (kernels/fastscan_kernel.py)"
MOVES = "p99_ms"
SOURCE = "device_trace"
KERNELS = ("fastscan_stream_topk",)


def read(run):
    return roofline.share(roofline.scan_bytes(run.sums, run.config),
                          run.kernel_seconds(KERNELS), run.peaks)
