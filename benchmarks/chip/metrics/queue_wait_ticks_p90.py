"""90th percentile of the ticks a request due in the window waited for a
slot or pages before its admission (host scheduler layer; moves
``ttft_p90_ms``).  Admission tick = first-token tick less the ticks its
prefill chunks take, one chunk per tick."""
from chipbench.stream import percentile


def read(run):
    waits = run.rec.queue_waits(run.cell.traffic["chunk"])
    return float(percentile(waits, 90)) if waits else None
