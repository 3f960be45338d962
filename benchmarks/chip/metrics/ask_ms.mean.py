"""Mean host time of the optimizer's ask (``core.optimizers``), from the
benchmark's span around every ask of the window that proposed."""


def read(ctx):
    w = ctx["window"]
    asks = ctx["spans"].durations("ask", w.t0, w.t1)
    return 1e3 * sum(asks) / len(asks) if asks else None
