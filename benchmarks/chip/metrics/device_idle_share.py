"""Share of the traced window in which no operation ran on the device
(device layer; moves ``output_tok_s``): 1 - union of the device op
intervals / traced window."""


def read(run):
    w = run.trace.window_s()
    return 100.0 * (1.0 - run.trace.busy_s() / w) if w > 0 else None
