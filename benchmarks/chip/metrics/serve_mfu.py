"""Model FLOP utilisation of the whole step over the window (whole step
layer; moves ``output_tok_s``): the operations the useful tokens need (each
prompt whose first token came in the window, each decode token of the
window with its context; matmuls, causal attention and the logits, no
padding, no parked rows) over window seconds times the chip's bf16 peak."""
from chipbench import flops


def read(run):
    rec, m = run.rec, run.model
    total = 0
    for rid, stamps in rec.stamps.items():
        lp = rec.stream.prompt_len[rid]
        for j, t in enumerate(stamps):
            if not rec.in_window(t):
                continue
            total += (flops.prefill_flops(m, lp) if j == 0
                      else flops.decode_flops(m, lp + j))
    if not total:
        return None
    return 100.0 * total / (rec.seconds * run.peak["bf16_flop_s"])
