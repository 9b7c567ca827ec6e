"""The fused SED pooling kernel's share of its roofline: the least time for
reading each graph's valid segment embeddings and masks and writing its
pooled row (counts.sed_pool), over the kernel's time in the trace, in
percent.  Bound by HBM bandwidth.  The trace names the kernel after its
jitted wrapper (``kernels/ops.py::sed_aggregate``)."""
import counts
import trace_reduce as TR

KERNEL = "sed_aggregate"


def read(run):
    t, calls = TR.kernel_s(run.trace, KERNEL)
    if not calls or t <= 0:
        return None
    batch = run.traffic["batch_size"] / run.chips
    c = counts.sed_pool(batch, run.stats["segments"], run.cfg["hidden"])
    least = max(c["flops"] / run.peak["bf16_flops_per_s"],
                c["bytes"] / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * calls / t
