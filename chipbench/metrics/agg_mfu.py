"""Whole-aggregate share of the chips' bf16 peak: the FLOPs MA-Echo
requires per aggregate (``chipbench.work``) times the aggregates the
untraced part of the window completed, over its host-clock time, over
chips times the peak."""
from chipbench import work


def read(ctx):
    calls = [c for c in ctx["calls"] if not c[2]] or ctx["calls"]
    elapsed = calls[-1][1] - calls[0][0]
    flops = ctx["work_per_call"]["flops"] * len(calls)
    return 100.0 * flops / elapsed / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
