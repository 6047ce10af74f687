"""Device idle time behind the host's own code, per decode step (device
layer; moves ``output_tok_s``): the stretches of the traced window in which
no operation ran on the first chip, where they overlap a ``serve.*`` span
other than the host's two waits on the device, ``serve.pick`` and
``serve.sync``, over the number of ``serve.sync`` spans.  The rest of the
idle time falls in those waits (the host waking up after the device's
result) or between spans.  Returns nothing when the trace holds no
``serve.sync`` span."""
from chipbench import trace

SYNC = "serve.sync"
WAITS = (SYNC, "serve.pick")


def read(run):
    tr = run.trace
    steps = sum(1 for name, _, _ in tr.host if name == SYNC)
    if not steps:
        return None
    idle = trace.gaps(tr.ops[tr.chips[0]], *tr.window_ns)
    host = [(s, e) for name, s, e in tr.host
            if name.startswith("serve.") and name not in WAITS]
    # |idle & host| = |idle| + |host| - |idle | host|
    both = (trace.union_ns(idle) + trace.union_ns(host)
            - trace.union_ns(idle + host))
    return both / 1e6 / steps
