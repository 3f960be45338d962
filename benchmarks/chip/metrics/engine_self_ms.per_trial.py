"""Self time of the engine's ``trial`` spans (a step less its ask, the
store's round trips, the measurement and the tell: batch bookkeeping, the
execution backend, the stopping rule), per trial completed in the
window."""

import program_spans


def read(ctx):
    return program_spans.per_trial_ms(ctx, ("trial",), self_time=True)
