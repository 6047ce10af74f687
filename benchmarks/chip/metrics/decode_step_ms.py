"""Device time of one execution of the paged decode program, averaged over
its executions in the trace (step programs layer; moves ``output_tok_s``).
Found by its XLA module name."""

MODULE = "jit_decode_paged"


def read(run):
    times = run.trace.module_times(MODULE)
    return 1e3 * sum(times) / len(times) if times else None
