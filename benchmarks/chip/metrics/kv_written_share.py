"""Pages written over pages reserved, summed over the engine's ticks in the
traced window (KV page manager layer; moves ``ttft_p90_ms``): admission
reserves each request's worst case, prompt and output, and the rest of the
reservation waits unwritten while it blocks admission.  Read from the
program's tick log (``repro.serving.telemetry``), stamped on the host clock
of ``run.traced``.  Returns nothing without that log or a reserved page."""


def read(run):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    recs = telemetry.records(*run.traced)
    reserved = sum(r.reserved_pages for r in recs)
    if not reserved:
        return None
    return 100.0 * sum(r.written_pages for r in recs) / reserved
