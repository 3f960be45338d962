"""Share of the roofline the TPE ask program reaches: the least time the
chip needs for the work of every scored ask of the traced window (counted
at the true good, bad and pool sizes by ``flops.tpe_ask``; bound by compute
at these sizes) over the device time of ``jit__tpe_scores``.  The peak is
the chip's bf16 matrix rate, while the Parzen ratio is float32 elementwise
work and exponentials that never reach the matrix units: the share reads
low by construction."""

import flops

PROGRAMS = ("jit__tpe_scores",)


def read(ctx):
    busy = sum(ctx["trace"]["module_time"].get(p, 0.0) for p in PROGRAMS)
    sizes = ctx["window"].record.sizes
    if busy <= 0 or not sizes:
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for good, bad, pool, num_dims, cat_dims in sizes:
        f, b = flops.tpe_ask(good, bad, pool, num_dims, cat_dims)
        least += max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
