"""Share of the roofline the BO-GP ask programs reach: the least time the
chip needs for the work of every scored ask of the traced window (counted
at the true history and pool sizes by ``flops.gp_ask``; bound by compute at
these sizes) over the device time of the ask programs.  The peak is the
chip's bf16 rate, while the ask runs float32 at "highest" precision, which
takes several bf16 passes: the share reads low by construction."""

import flops

PROGRAMS = ("jit__gp_fit", "jit__gp_ei")


def read(ctx):
    busy = sum(ctx["trace"]["module_time"].get(p, 0.0) for p in PROGRAMS)
    sizes = ctx["window"].record.sizes
    if busy <= 0 or not sizes:
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for n, pool, dims, refit in sizes:
        f, b = flops.gp_ask(n, pool, dims, refit)
        least += max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
