#!/usr/bin/env python3
"""Does a change leave a cell's train step alone? Compile the step of one
benchmark cell deviceless for a described v5e, from the tree at <root>,
and write the optimized HLO with what differs between any two checkouts
stripped: `metadata={...}`, the source tables, and the Pallas kernels'
payloads (which embed the source lines of `ops/pallas_kernels.py`).

Run it on both trees UNDER ONE PATH (a symlink swapped between them: a
payload also holds the checkout's path) and compare the two files:

    ln -sfn /root/repo /root/scratch/tree
    python scripts/same_step.py /root/scratch/tree <cell> change.txt
    ln -sfn <parent checkout> /root/scratch/tree
    python scripts/same_step.py /root/scratch/tree <cell> parent.txt
    cmp change.txt parent.txt

Everything is imported from <root>, wherever this file lies. The model
is built on the CPU at the cell's full size through the family's `build`
with the search told the machine is a v5e, the step's arguments are what
`fit` hands it (caught at the first dispatch), and the kernels' TPU
branch is taken although the live backend is the CPU. Nothing runs;
nothing here is a measurement. (PR 43: both `bert_ae` cells, Inception
and joyai: 0 differing lines.)
"""

import hashlib
import os
import re
import sys

SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


class _Caught(Exception):
    """The step's arguments are in hand: `fit` need go no further."""


def stripped(hlo):
    """The text without what names a checkout or a source line."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    lines = (re.sub(r"backend_config=\{.*$", "", line)
             if "tpu_custom_call" in line else line
             for line in hlo.splitlines())
    return "\n".join(line for line in lines
                     if not line.startswith(SOURCE_TABLES)
                     and not re.match(r"^\d+ ", line))


def main():
    root, cell, out = sys.argv[1:4]
    chips = 4 if cell.endswith("4chip") else 1
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={chips}")
    sys.path.insert(0, root)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import flexflow_tpu
    from benchmarks import manifest as mf
    from benchmarks.harness import build_native, load_by_path
    from flexflow_tpu.machine import MachineSpec
    from flexflow_tpu.ops import pallas_kernels as pk

    assert flexflow_tpu.__file__.startswith(root), flexflow_tpu.__file__
    pk.pallas_mode = lambda: "tpu"      # the live backend is the CPU
    _, config, traffic = mf.find_cell(mf.load_manifest(root), cell, root)
    family = load_by_path("families", config["family"], root)
    build_native(root)
    s = family.sizes(config, traffic, None)
    xs, y = family.make_data(s, 7)
    ff = family.build(config, s, chips, 7, machine_spec=MachineSpec(
        "tpu-v5e", chips_per_slice=chips))
    ex = ff.executor

    kept = {}

    def catch(*args):
        kept["args"] = args
        raise _Caught

    ex.make_train_step = lambda: catch
    xs = xs if isinstance(xs, (list, tuple)) else [xs]
    try:
        ff.fit([x[:s["batch"]] for x in xs], y[:s["batch"]], epochs=1,
               verbose=False)
    except _Caught:
        pass

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    axes = dict(zip(ex.mesh.axis_names, ex.mesh.devices.shape))
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(
        tuple(axes.values())), tuple(axes))
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, getattr(
            getattr(a, "sharding", None), "spec", P()))), kept["args"])
    ex.mesh = mesh
    hlo = stripped(jax.jit(ex._train_step_fn(), donate_argnums=(0, 1, 2))
                   .lower(*args).compile().as_text())
    with open(out, "w") as f:
        f.write(hlo)
    print(cell, hashlib.sha256(hlo.encode()).hexdigest()[:16],
          len(hlo.splitlines()), "lines; choices",
          sorted({getattr(st, "choice", None) or ""
                  for st in ff.strategy.values()}))


if __name__ == "__main__":
    main()
