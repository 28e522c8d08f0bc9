"""Test-only entry: one cell end to end on the CPU at a tiny size.

Not reachable from `benchmarks/run.py`. Nothing it prints is a device
number: the platform in every line is `cpu`.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tests/chipbench/rehearse.py <cell> [0|1|2]
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "bert_ae": dict(num_hidden_layers=2, hidden_size=64,
                    num_attention_heads=4, seq=32, batch=8,
                    steps_per_epoch=2),
    "inception_v3_ae": dict(image_size=75, num_classes=10, batch=4,
                            steps_per_epoch=2),
}


def send_output_to(monkeypatch, directory):
    """Point the harness and the readers of the session's metrics at
    `directory` instead of the checkout's `chipbench_out/`."""
    from benchmarks import harness, session_reduce
    for module in (harness, session_reduce):
        monkeypatch.setattr(module, "OUT_DIR", str(directory))


def rehearse(cell, trace, seed=7, seconds=0.5, root=ROOT):
    from benchmarks import manifest as mf
    from benchmarks.harness import run_cell
    _, config, _ = mf.find_cell(mf.load_manifest(root), cell, root)
    return run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                    root=root, rehearsal=dict(sizes=TINY[config["family"]]))


if __name__ == "__main__":
    rehearse(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0)
