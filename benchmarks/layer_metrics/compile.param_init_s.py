"""Host seconds of `FFModel.compile`'s parameter-init and state-placement
phases (`compile_phases` in the header of the session's artifact)."""

from benchmarks import session_reduce as sr


def read(ctx):
    session = sr.find(ctx, __file__)
    phases = session and session.header.get("compile_phases")
    if not phases:
        return None
    return phases["param_init_s"] + phases["state_placement_s"]
