"""Device time per dispatched batch: the union of the trace's device
operation intervals over the batches the loop dispatched in the window."""
NAME = "device.busy_ms_per_batch.trickle"
UNIT = "ms"
LAYER = "device"
MOVES = "p50_ms"
SOURCE = "device_trace"


def read(run):
    if not run.trace.busy_s or not run.loop["batches"]:
        return None
    return 1e3 * run.trace.busy_s / run.loop["batches"]
