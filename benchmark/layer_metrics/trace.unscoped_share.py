"""Share of the device's busy time that no ``tac/`` scope names: operations of
other programs, operations the scope table does not know or gives no scope,
and fusions that span two groups.  What the instrument still cannot name.
Denominator: the trace's ``busy_s`` (the union of all events), not the leaf
time the groups partition: the busy time under a container that no operation
covers (``container_gap_s``) is in it and in no group, this one included."""

from benchmark.harness import scopes


def read(ctx):
    s = scopes.summary(ctx)
    if s is None or not ctx.trace["busy_s"]:
        return None
    return 100.0 * s["device"][scopes.UNSCOPED] / ctx.trace["busy_s"]
