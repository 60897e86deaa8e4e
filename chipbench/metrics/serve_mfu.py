"""Whole-serving share of the chips' bf16 peak: the forward FLOPs every
request requires (prefill of its prompt, then one step per further
token; ``chipbench.work``) over the untraced calls' host-clock time,
over chips times the peak."""


def read(ctx):
    calls = [c for c in ctx["calls"] if not c[2]] or ctx["calls"]
    elapsed = calls[-1][1] - calls[0][0]
    flops = ctx["flops_per_request"] * ctx["requests_per_call"] * len(calls)
    return 100.0 * flops / elapsed / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
