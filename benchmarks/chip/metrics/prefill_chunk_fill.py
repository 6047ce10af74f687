"""Share of the ``(1, chunk)`` prefill calls' token positions that hold
prompt tokens, over the traced window (step programs layer; moves
``ttft_p90_ms``): a prompt's last chunk is padded to the fixed shape.  Read
from the program's tick log (``repro.serving.telemetry``), stamped on the
host clock of ``run.traced``.  Returns nothing without that log or a
chunk."""


def read(run):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    recs = telemetry.records(*run.traced)
    chunks = sum(r.chunks for r in recs)
    if not chunks:
        return None
    return (100.0 * sum(r.chunk_tokens for r in recs)
            / (chunks * run.cell.traffic["chunk"]))
