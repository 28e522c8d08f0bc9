"""What the deviceless compiles of `tests/test_tpu_compile*.py` share: the
described TPU v5e 2x2 (`jax.experimental.topologies`: the TPU compiler
ships with the installation and compiles for a topology that is
described, not attached), the fixtures that steer code to its TPU branch
and keep the persistent cache away, and the readers of a compiled
program's text. The three files import the fixtures by name; they are
three so that three workers can take them (ROADMAP D10). One process at
a time may load the TPU's library unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1`
is set around the run, as the driver's command sets it: without it a
run on several workers describes the chip in the first of them and
SKIPS the other two files' tests (each file alone runs anywhere). It is
set outside, never here: on a machine with a chip that lock is what
keeps two processes off one chip."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from flexflow_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_compilation_cache():
    """A deviceless executable can be written to the persistent cache
    but not read back without a chip; keep the cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the kernels' TPU branch although the live backend is CPU."""
    monkeypatch.delenv("FLEXFLOW_TPU_PALLAS", raising=False)
    monkeypatch.setattr(pk, "pallas_mode", lambda: "tpu")


def described_mesh(topo, axes):
    n = int(np.prod(list(axes.values())))
    devs = np.array(topo.devices[:n]).reshape(tuple(axes.values()))
    return Mesh(devs, tuple(axes))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def abstract_op(topo, op):
    """(parameters, inputs) of ``op`` as shapes on one described chip,
    bfloat16 but for the leaves the op states float32."""
    one = SingleDeviceSharding(topo.devices[0])
    params = {
        leaf: jax.ShapeDtypeStruct(
            a.shape, jnp.float32 if leaf in op.full_precision_params
            else jnp.bfloat16, sharding=one)
        for leaf, a in jax.eval_shape(
            op.init_params, jax.random.PRNGKey(0)).items()}
    inputs = tuple(jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
                   for shape in op.input_shapes)
    return params, inputs


_BYTES = {"bf16": 2, "f32": 4}
_ARRAY = re.compile(r"(bf16|f32)\[([\d,]+)\]\{([\d,]+)")


def layout_faults(hlo, big, weights=()):
    """What the [B, S, H*D] operand form exists to remove from a compiled
    step: `copy` instructions whose result holds ``big`` bytes or more (a
    whole q, k, v or o changing layout; a result shaped like one of
    ``weights`` is a parameter's copy and none of this), and operands or
    results of a flash kernel whose minor dimension is narrower than the
    128 lanes it is padded to in HBM."""
    faults = []
    weights = {",".join(map(str, w)) for w in weights}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (bf16|f32)\[([\d,]+)\]"
                     r"\S* copy\(", line)
        if m and m.group(3) not in weights and (
                int(np.prod([int(d) for d in m.group(3).split(",")]))
                * _BYTES[m.group(2)] >= big):
            faults.append(f"copy {m.group(1)} {m.group(2)}[{m.group(3)}]")
        if ('custom_call_target="tpu_custom_call"' in line
                and "flash_" in line.split("metadata=")[-1][:200]):
            for dt, dims, layout in _ARRAY.findall(line.split("metadata=")[0]):
                dims = [int(d) for d in dims.split(",")]
                if dims[int(layout.split(",")[0])] < pk.LANES:
                    faults.append(f"flash operand {dt}{dims}{{{layout}}}")
    return faults
