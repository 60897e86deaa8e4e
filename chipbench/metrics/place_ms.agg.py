"""Host milliseconds in which the traced aggregate's client checkpoints
and projectors become device arrays: the duration of the program's
``maecho.place`` span (``init_global``, the anchor and projector
stacks, the multi-level flatten) inside the traced call.  Nothing where
the program records no such span."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    t0, t1 = next((c[0], c[1]) for c in ctx["calls"] if c[2])
    recs = [r for r in spans.records(int(t0 * 1e9), int(t1 * 1e9))
            if r.name == "maecho.place"]
    if not recs:
        return None
    return sum(r.end_ns - r.start_ns for r in recs) / 1e6
