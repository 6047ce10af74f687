"""Device time of one execution of the ``(1, chunk)`` prefill program,
averaged over its executions in the trace (step programs layer; moves
``ttft_p90_ms``).  Found by its XLA module name."""

MODULE = "jit_chunk_prefill"


def read(run):
    times = run.trace.module_times(MODULE)
    return 1e3 * sum(times) / len(times) if times else None
