"""GiB the traced aggregate's placement put onto the fullest device: the
program's ``maecho.place_bytes`` counter (``device``, ``n``), summed per
device, at its largest.  Nothing where the program records no such
counter in the call."""

GIB = 2 ** 30


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    t0, t1 = next((c[0], c[1]) for c in ctx["calls"] if c[2])
    per: dict = {}
    for r in spans.records(int(t0 * 1e9), int(t1 * 1e9)):
        if r.name == "maecho.place_bytes":
            per[r.attrs["device"]] = per.get(r.attrs["device"], 0) + \
                r.attrs["n"]
    if not per:
        return None
    return max(per.values()) / GIB
