"""Share of the traced window in which no operation ran on the chip: 1 minus
the union of the trace's device operation intervals over the window."""
NAME = "device.idle_share.bulk"
UNIT = "%"
LAYER = "device"
MOVES = "qps"
SOURCE = "device_trace"


def read(run):
    if not run.trace.busy_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.window_s)
