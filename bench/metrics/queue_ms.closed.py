"""Front end: mean time a request waits in its tenant queue, from submit to
dispatch (``favor_frontend_queue_seconds``), closed loop."""


def read(ctx):
    total, n = ctx.hist("favor_frontend_queue_seconds")
    return 1e3 * total / n if n else None
