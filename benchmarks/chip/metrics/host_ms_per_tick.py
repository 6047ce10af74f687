"""Host time the serving engine spends in its own code per decode step (host
scheduler layer; moves ``itl_p90_ms``): the union of the program's
``serve.*`` spans in the traced window other than its two waits on the
device, ``serve.pick`` (the first tokens of completed prompts) and
``serve.sync`` (the decode step's tokens) -- that is admission, prefill
calls, input preparation, dispatch and token emit, a collector pause inside
one of them counted once -- over the number of ``serve.sync`` spans, one per
decode step.  Returns nothing when the trace holds no ``serve.sync`` span."""
from chipbench import trace

SYNC = "serve.sync"
WAITS = (SYNC, "serve.pick")


def read(run):
    host = [(s, e) for name, s, e in run.trace.host
            if name.startswith("serve.") and name not in WAITS]
    steps = sum(1 for name, _, _ in run.trace.host if name == SYNC)
    if not steps:
        return None
    return trace.union_ns(host) / 1e6 / steps
