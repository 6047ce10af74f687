"""Model FLOP utilisation of the whole step over the window, for a hybrid
stack of full and sliding-window layers with an expert share (whole step
layer; moves ``output_tok_s``): the operations the useful tokens need (each
prompt whose first token came in the window, each decode token of the window
with its context; ``chipbench.flops_hybrid``: attention at the keys each
layer reads, the dense MLP, the shared expert, the router and the routed
experts that land on this chip's share, the logits; no padding, no parked
rows) over window seconds times the chip's bf16 peak."""
from chipbench import flops_hybrid


def read(run):
    rec, m = run.rec, run.model
    total = 0
    for rid, stamps in rec.stamps.items():
        lp = rec.stream.prompt_len[rid]
        for j, t in enumerate(stamps):
            if not rec.in_window(t):
                continue
            total += (flops_hybrid.prefill_flops(m, lp) if j == 0
                      else flops_hybrid.decode_flops(m, lp + j))
    if not total:
        return None
    return 100.0 * total / (rec.seconds * run.peak["bf16_flop_s"])
