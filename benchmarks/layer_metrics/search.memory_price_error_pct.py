"""How far the search's price of the executed strategy's memory lies from
the allocator: 100 |Pm - A| / A, Pm the bytes a chip of the native
simulator's replay (`prices.memory_bytes`), A `device_peak_bytes` in the
header of the session's artifact (`peak_bytes_in_use` plus
`peak_bytes_reserved` on the fullest device; `benchmarks/step_prices.py`).
An over-count makes the search checkpoint ops that need it not, an
under-count tells a job it fits when it does not."""

from benchmarks import step_prices


def read(ctx):
    return step_prices.memory_price_error_pct(ctx, __file__)
