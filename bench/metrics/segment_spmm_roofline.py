"""The one-hot SpMM kernel's share of its roofline: the least time the chip
needs for the algorithm's own gathers, scalings and scatters of the valid
edges (counts.segment_spmm, forward and backward calls alike), over the
kernel's time in the trace, in percent.  Bound by HBM bandwidth at these
shapes.  The trace names the kernel after its jitted wrapper
(``kernels/ops.py::batched_neighbor_sum``)."""
import counts
import trace_reduce as TR

KERNEL = "batched_neighbor_sum"


def read(run):
    t, calls = TR.kernel_s(run.trace, KERNEL)
    if not calls or t <= 0:
        return None
    n_seg = run.traffic["batch_size"] * run.traffic["num_sampled"] / run.chips
    c = counts.segment_spmm(n_seg, run.stats["nodes"], run.stats["edges"],
                            run.cfg["hidden"])
    least = max(c["flops"] / run.peak["bf16_flops_per_s"],
                c["bytes"] / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * calls / t
