"""Share of its roofline that the paged decode-attention kernel reaches
(kernels layer; moves ``output_tok_s``): the least time for the work the
decode rows of the traced window need (live K/V pages, queries and outputs
in bytes; QK^T and PV in operations; the larger bound) over the kernel's
summed device time in the trace.  Parked rows and dead table entries are no
work.  Returns nothing when the trace holds no kernel execution."""
from chipbench import flops

# the Pallas call has no name yet: the paged kernel is the custom call whose
# first operand is the (slots, pages) int32 block table
KERNEL = r"custom-call\(s32\[\d+,\d+\]"


def read(run):
    kernel_s, n = run.trace.op_time(KERNEL)
    if not n or kernel_s <= 0:
        return None
    t0, t1 = run.traced
    ctx = [c for _, c in run.rec.decode_rows(t0, t1)]
    work = flops.paged_attn_work(run.model, ctx)
    return 100.0 * flops.roofline_s(*work, run.peak) / kernel_s
