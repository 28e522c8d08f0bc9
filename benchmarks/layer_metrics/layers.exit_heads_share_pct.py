"""Share of the traced train steps' device-busy time in what a looped
model's passes share at the end: the parts `exit` (the ops the builder
put under `FFModel.scope("exit")`: the T passes' sequences laid end to
end, the ONE head product over them, the exit gate's column, the
output's concatenation), `head` (the op that produces the model's
output, where it lies outside that scope) and `loss` (the targets'
log-probabilities over T * S rows, the exit distribution, the mixture
and its entropy), forward and backward, by the join table the program
writes (`benchmarks/step_parts.py`). Where the program names no `exit`
part the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    if not step_parts.part_share_pct(ctx, __file__, ("exit",)):
        return None
    return step_parts.part_share_pct(ctx, __file__, ("exit", "head", "loss"))
