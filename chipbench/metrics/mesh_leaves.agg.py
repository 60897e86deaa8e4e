"""Leaves the aggregation plan that ran in the traced aggregate sent down
a mesh route (``sharded`` or ``sharded2d``): the program's
``maecho.route`` counter (a count).  Nothing where the program records
no such counter in the call."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    t0, t1 = next((c[0], c[1]) for c in ctx["calls"] if c[2])
    recs = [r for r in spans.records(int(t0 * 1e9), int(t1 * 1e9))
            if r.name == "maecho.route"]
    if not recs:
        return None
    return sum(r.attrs["n"] for r in recs
               if r.attrs["route"] in ("sharded", "sharded2d"))
