"""expert matmul: share of its roofline.  The FLOPs the held pairs of the
window need in the grouped matmul (`bench/moe_flops.gmm_flops_per_pair`:
three d x f matmuls, forward and backward, no recomputation) over the
device time of the grouped-matmul operations (every op whose name holds
OP), over one chip's bf16 peak.  The kernel is bound by compute at these
shapes: its bytes (the weights once a row tile, the rows once) need a
tenth of the time its FLOPs do."""

#: the fragment of the grouped matmul's op names in the trace
OP = "gmm"


def read(run):
    pairs = run.counters.get("moe_held_pairs")
    if not pairs or not run.trace:
        return None
    t = sum(s for n, s in run.trace.op_s.items() if OP in n)
    if t <= 0:
        return None
    need = run.counters["gmm_flops_per_pair"] * pairs
    return 100.0 * need / run.peak.bf16_flops_per_s / t
