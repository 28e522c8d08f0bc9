"""Pins of the programs PR 47 must not have touched.

PR 47 taught the flash kernels and `rotary_lanes` a second value of one
parameter, heads a 128-lane block (2, at heads of 64, beside 1), which
the code reads from its operands' shapes. Where that parameter is 1, or
the keys are not grouped, the traced program has to be the parent's
(`d773349`, PR 46), jaxpr and all: the kernel bodies, the block shapes,
the grids, the operands around the calls. `PINS` holds a digest of each
such jaxpr (forward and every gradient through the public call), taken
on the parent's tree with this file's `digest`; hexadecimal addresses
are struck out first.

A PR that changes one of these programs ON PURPOSE prints the new table
with `python tests/test_kernel_jaxpr_pins.py` and says so; a PR that
only meant to add a form finds here that it did more.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.ops import pallas_kernels as pk


def digest(fn, *args) -> str:
    # a product's `precision` is part of its equation: traced at the
    # default whatever an earlier test of this process left behind (a
    # `jax.default_matmul_precision` object entered inside itself
    # restores "highest", not None)
    with jax.default_matmul_precision(None):
        text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def flash(seq, h, hk, d, **mask):
    """`flash_attention` and its three gradients, causal, bfloat16."""
    q = jnp.zeros((1, seq, h * d), jnp.bfloat16)
    k = jnp.zeros((1, seq, hk * d), jnp.bfloat16)

    def run(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
            q, k, v, h, True, num_kv_heads=hk, **mask).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    return digest(run, q, k, k)


def rotary(seq, h, d, r, normed):
    """`rotary_lanes` and its gradients (the norm's scale too)."""
    x = jnp.zeros((1, seq, h * d), jnp.float32)
    cos = jnp.zeros((seq, d), jnp.float32)

    def run(x, scale):
        return jax.grad(lambda x, s: jnp.sum(pk.rotary_lanes(
            x, cos, cos, r // 2, jnp.bfloat16,
            norm=(s, 1e-6) if normed else None).astype(jnp.float32)),
            argnums=(0, 1))(x, scale)
    return digest(run, x, jnp.ones((d,), jnp.float32))


# name -> (what to trace, the digest on the parent's tree)
PINS = {
    # every head its own keys, heads of 128: the whole-tile kernels
    "flash_whole_4_4_128": (lambda: flash(256, 4, 4, 128),
                            "7d82703d3eb18ad0"),
    # grouped keys at heads of 128 (PR 43) in the three kernel families
    "flash_whole_8_2_128": (lambda: flash(256, 8, 2, 128),
                            "54ddd10fdcbbea9a"),
    # the chunk-loop kernels: moved ON PURPOSE by PR 51 (a grid step's
    # body is a super-block's, the backward's chunks may run as
    # sub-blocks: `pallas_kernels.super_block`; at S 1280 the rule gives
    # one block a step and the values are the parent's bit for bit,
    # `tests/test_flash_kernels.py`); PR 46's digest was 39333fa460ad8def
    "flash_blocked_8_2_128": (lambda: flash(1280, 8, 2, 128),
                              "ec2e5424166d584b"),
    "flash_span_8_2_128": (lambda: flash(1536, 8, 2, 128, window=128),
                           "34723cece079bc2a"),
    # heads of 64, every head its own keys: bert_ae's form, the control
    "flash_whole_4_4_64": (lambda: flash(256, 4, 4, 64),
                           "2a1b70ccb08cedec"),
    # (PR 51, as above; PR 46's was 700c0b0e0a675e45)
    "flash_blocked_4_4_64": (lambda: flash(1280, 4, 4, 64),
                             "e64e476a0f224d85"),
    # the pass at heads of 128: whole and partial rotary, with the norm
    "rotary_whole_128": (lambda: rotary(256, 3, 128, 128, False),
                         "c225bef5cc5849c9"),
    "rotary_partial_128": (lambda: rotary(256, 3, 128, 64, False),
                           "653294afc68a287d"),
    "rotary_normed_128": (lambda: rotary(256, 3, 128, 128, True),
                          "bdb6a39c535e500f"),
}


@pytest.mark.parametrize("name", list(PINS))
def test_one_head_a_lane_block_traces_the_parents_program(name, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    trace, want = PINS[name]
    assert trace() == want, (
        f"the jaxpr of {name} is not PR 46's: see this file's docstring")


if __name__ == "__main__":
    import os

    os.environ["FLEXFLOW_TPU_PALLAS"] = "interpret"
    for name, (trace, was) in PINS.items():
        print(f"{name}: {trace()} (pinned {was})")
