"""Host time of the ask's GP calls (``accel.gp_jax``): the fit
(``ask.fit``: padding, dispatch, the NaN check that waits on the device)
and the expected improvement (``ask.ei``: padding, dispatch, readback),
device wait included, per ask of the window.  Less
``gp_device_ms.per_ask``, it is dispatch, copies and synchronisation."""

import program_spans


def read(ctx):
    return program_spans.per_ask_ms(ctx, ("ask.fit", "ask.ei"))
