"""Real requests per dispatched batch over the traced window, from the
serving loop's own counters (``LoopMetrics.rows_served`` / ``.batches``).
Fuller batches amortise the scan's fixed cost; emptier ones mean requests
are dispatched sooner."""
NAME = "batcher.rows_per_batch"
UNIT = "rows"
LAYER = "serving batcher (serving/batcher.py)"
MOVES = "p99_ms"
SOURCE = "program_counter"


def read(run):
    batches = run.loop["batches"]
    if not batches:
        return None
    return run.loop["rows_served"] / batches
