"""Share of the traced tail's window (first train-step program's start to
the last one's end, as `device.idle_pct`) in which the device is idle
while the host is inside a `data_load` or `device_put` span; per device,
then the mean. Those spans cover the batch's enqueue only: idle time in
which the device waits for the copy itself falls to the span the host
has moved on to (`idle_in_dispatch`, `idle_in_metrics_sync` in
`breakdown.idle_shares_pct`). So this is the share a faster `next_batch`
on the host thread could remove, and no more."""

from benchmarks import session_reduce as sr


def read(ctx):
    session = sr.find(ctx, __file__)
    if not sr.tied(session):
        return None
    shares = sr.idle_shares_pct(ctx["devices"], session.spans)
    return None if shares is None else sr.share_of(shares, sr.STAGING)
