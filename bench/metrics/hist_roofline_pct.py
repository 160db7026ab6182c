"""histogram kernel: share of its roofline.  Any fold must read the two
float32 counters of every sample once from HBM (8 bytes a sample); the
least time for that at the chip's HBM peak, over the device time of the
`_hist_pallas` programs (kernel, bucket fold and any copy inside them).
The fold is bound by memory: its compares and adds have no published
vector-unit peak to be held against."""

BYTES_PER_SAMPLE = 8


def read(run):
    n = run.counters.get("rounds")
    t = run.trace.program_time("_hist_pallas") if run.trace else 0.0
    if not n or t <= 0:
        return None
    need = BYTES_PER_SAMPLE * run.counters["samples_per_round"] * n
    return 100.0 * need / run.peak.hbm_bytes_per_s / t
