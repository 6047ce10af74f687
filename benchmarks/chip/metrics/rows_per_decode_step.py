"""Decode rows per decode step in the traced window: tokens the decode steps
streamed over the number of decode steps (host scheduler layer; moves
``output_tok_s``).  Counted from the token stream, which gives one token per
decoding request per step; parked slots are not rows."""


def read(run):
    t0, t1 = run.traced
    steps = run.rec.decode_steps(t0, t1)
    if not steps:
        return None
    return len(run.rec.decode_rows(t0, t1)) / steps
