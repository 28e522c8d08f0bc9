"""Share of the traced tail's window in which the device is idle and no
program span other than the frames `fit` and `step` is open on the host
(the stretch between two `fit` calls included): the idle time that the
program's tracing does not explain yet."""

from benchmarks import session_reduce as sr


def read(ctx):
    session = sr.find(ctx, __file__)
    if not sr.tied(session):
        return None
    shares = sr.idle_shares_pct(ctx["devices"], session.spans)
    return None if shares is None else sr.unnamed_share(shares)
