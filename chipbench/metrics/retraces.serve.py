"""Traces of the serving call's jitted functions inside the traced
call: the increments of the program's ``serve.trace`` counter, which
the batch-1 prefill, the slot insert and the serve step (per live-window
bucket) count while JAX traces them.  Nothing where the program records
nothing in the call."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    t0, t1 = next((c[0], c[1]) for c in ctx["calls"] if c[2])
    recs = spans.records(int(t0 * 1e9), int(t1 * 1e9))
    if not recs:
        return None
    return sum(r.attrs["n"] for r in recs if r.name == "serve.trace")
