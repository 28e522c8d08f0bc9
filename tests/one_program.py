"""What a test costs on the CPU is the programs it compiles, not the
size of their operands (ROADMAP D10): a `jnp` call outside `jax.jit`
compiles one program an operation and shape, a `jax.grad` outside it one
for every primitive of the forward and of the backward. The helper here
runs a function's output and its gradients as ONE program; operands and
expectations stay numpy arrays."""

import jax
import jax.numpy as jnp


def output_and_gradients(fn, weight, *operands, argnums=None):
    """(fn(*operands), the gradients of sum(fn(*operands) * weight) with
    respect to ``argnums``, every operand where None), one compiled
    program. Where ``fn`` returns a tuple its first element is the one
    weighted, and the whole tuple comes back."""
    if argnums is None:
        argnums = tuple(range(len(operands)))

    def loss(*xs):
        out = fn(*xs)
        first = out[0] if isinstance(out, tuple) else out
        return jnp.sum(first.astype(jnp.float32) * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=argnums, has_aux=True))(*operands)
    return out, grads
