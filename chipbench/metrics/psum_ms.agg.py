"""Device milliseconds of all-reduce operations (the Gram psums of the
mesh routes, and whatever else the compiler reduces across chips) in the
traced aggregate, on the fullest chip.  Nothing where the trace holds
no all-reduce."""
from chipbench import trace as T


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = tr.op_ns(T.is_all_reduce)
    return ns / 1e6 if ns > 0 else None
