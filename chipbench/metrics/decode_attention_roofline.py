"""Share of the roofline the decode-attention kernel
(``kernels/decode_attention``) reaches in the traced serving call: the
least time for the live-window K/V bytes and FLOPs the call's decode
steps require (``chipbench.work``), over the kernel's device time."""
from chipbench import trace as T
from chipbench import work


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = tr.op_ns(T.is_decode_attention)
    if ns <= 0:
        return None
    per = ctx["decode_attention_per_request"]
    n = ctx["requests_per_call"]
    need = {"flops": per["flops"] * n, "bytes": per["bytes"] * n}
    least, _bound = work.least_seconds(need, ctx["peaks"], 1)
    return 100.0 * least / (ns / 1e9)
