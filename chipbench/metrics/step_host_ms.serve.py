"""Host milliseconds of the traced serving call's decode loop in which
no step is queued on the device: the self time of the program's
``serve.step`` spans (the step's dispatch and the bookkeeping around
it), each span's duration less that of its ``serve.sync`` child (the
token readback, while the step runs), summed over the call.  Nothing
where the program records no such span."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    t0, t1 = next((c[0], c[1]) for c in ctx["calls"] if c[2])
    recs = spans.records(int(t0 * 1e9), int(t1 * 1e9))
    steps = {r.id: r.end_ns - r.start_ns for r in recs
             if r.name == "serve.step"}
    if not steps:
        return None
    sync = sum(r.end_ns - r.start_ns for r in recs
               if r.name == "serve.sync" and r.parent_id in steps)
    return (sum(steps.values()) - sync) / 1e6
