"""Device time of the TPE ask program (``accel.tpe_jax``: the jitted
Parzen ratio over the pool) in the traced window, per ask."""

PROGRAMS = ("jit__tpe_scores",)


def read(ctx):
    w = ctx["window"]
    asks = len(ctx["spans"].durations("ask", w.t0, w.t1))
    busy = sum(ctx["trace"]["module_time"].get(p, 0.0) for p in PROGRAMS)
    if not asks or busy <= 0:
        return None
    return 1e3 * busy / asks
