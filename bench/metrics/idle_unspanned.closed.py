"""Device: share (%) of the device's idle time in the traced window during
which no ``favor.*`` program span was open on any host thread (``spans``),
closed loop."""
from spans import UNSPANNED, load


def read(ctx):
    r = load(notes=ctx.notes)
    if r is None or not r["idle_by_span"] or r["idle_s"] <= 0:
        return None
    return 100.0 * r["idle_by_span"].get(UNSPANNED, 0.0) / r["idle_s"]
