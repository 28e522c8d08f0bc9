"""How far the search's price of the executed step lies from the chip:
100 |P - M| / M, P the step time of the native simulator's replay of the
executed strategy (`prices.step_s` beside the join table the program
writes), M the train-step programs' busy milliseconds a step in the
device trace (`benchmarks/step_prices.py`)."""

from benchmarks import step_prices


def read(ctx):
    return step_prices.step_price_error_pct(ctx, __file__)
