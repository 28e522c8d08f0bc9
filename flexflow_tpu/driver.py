"""CLI launcher: ``python -m flexflow_tpu.driver [flags] script.py [args]``.

Analog of the reference's flexflow_python / flexflow/driver.py (SURVEY §1
L8): consume FFConfig flags, expose the parsed config to the script via
``flexflow_tpu.driver.get_config()``, then exec the script with the
remaining argv — so reference-style launch lines carry over:

    python -m flexflow_tpu.driver -b 64 --budget 30 my_model.py --my-flag
"""

from __future__ import annotations

import runpy
import sys
from typing import Optional

from flexflow_tpu.config import FFConfig

_config: Optional[FFConfig] = None


def get_config() -> FFConfig:
    """The FFConfig parsed by the launcher (fresh default outside it)."""
    global _config
    if _config is None:
        _config = FFConfig()
    return _config


def main(argv=None) -> int:
    global _config
    argv = list(sys.argv[1:] if argv is None else argv)
    cfg = FFConfig()
    rest = cfg.parse_args(argv)
    script = next((a for a in rest if a.endswith(".py")), None)
    if script is None:
        print("usage: python -m flexflow_tpu.driver [flags] script.py [args]",
              file=sys.stderr)
        return 2
    rest.remove(script)
    _config = cfg
    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    # multi-host launch (--nodes N > 1, one driver process per host):
    # rendezvous through the JAX distributed runtime before the script
    # builds any mesh, so jax.devices() spans all hosts
    from flexflow_tpu import distributed
    distributed.initialize_from_config(cfg)
    sys.argv = [script] + rest
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
