"""Share of the chip's roofline the MA-Echo kernels reach: the least
time the chips need for the work MA-Echo requires of the leaves on
kernel routes (``chipbench.work``: FLOPs at the bf16 peak, or bytes at
the HBM bandwidth, whichever bounds), over the kernels' device time in
the traced aggregate, summed over the chips."""
from chipbench import trace as T
from chipbench import work


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = tr.op_ns(T.is_maecho_kernel, reduce=sum)
    if ns <= 0:
        return None
    least, _bound = work.least_seconds(ctx["kernel_work_per_call"],
                                       ctx["peaks"], 1)
    return 100.0 * least / (ns / 1e9)
