"""Share of the traced train steps' device-busy time that lies in (part,
direction) rows whose price is within a factor of two of the measured
milliseconds (`benchmarks/step_prices.py`): how much of the step the cost
model prices sanely. `step_prices.json` in the session's directory says
which parts are not."""

from benchmarks import step_prices


def read(ctx):
    return step_prices.priced_within_2x_share_pct(ctx, __file__)
