"""Leaves the compiled aggregation plan sends to the jnp oracle route
instead of a Pallas kernel route: ``core.maecho.dispatch_summary`` of the
plan the window runs, read at set-up (a count)."""


def read(ctx):
    return ctx["counters"]["oracle_leaves"]
