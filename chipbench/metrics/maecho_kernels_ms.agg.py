"""Device milliseconds per aggregate in the MA-Echo Pallas kernels
(``kernels/maecho_gram``, ``maecho_update``, ``maecho_v_update``), on the
fullest chip of the traced aggregate."""
from chipbench import trace as T


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = tr.op_ns(T.is_maecho_kernel)
    return ns / 1e6 if ns > 0 else None
