"""Artifact conventions shared by every obs writer.

Each JSON artifact carries a ``header`` stamped with the framework
version (ISSUE satellite: traces must be attributable to the build that
produced them), the JAX platform, host identity, and a wall-clock
timestamp. Writes are atomic (write-temp-then-rename) so a crashed run
never leaves a half-written trace for the next tool to choke on.
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Any, Dict, Optional


def artifact_header(host_id: Optional[int] = None,
                    kind: Optional[str] = None) -> Dict[str, Any]:
    """Provenance header every trace/census/drift artifact embeds."""
    from flexflow_tpu.version import __version__

    try:
        import jax
        platform = jax.devices()[0].platform
        device = getattr(jax.devices()[0], "device_kind", platform)
        if host_id is None:
            host_id = jax.process_index()
    except Exception:  # pre-backend-init callers (pure unit tests)
        platform, device = "unknown", "unknown"
        host_id = host_id or 0
    header = dict(
        flexflow_tpu_version=__version__,
        created_unix=time.time(),
        platform=platform,
        device=device,
        host_id=int(host_id),
    )
    if kind:
        header["kind"] = kind
    return header


def atomic_write_text(path: str, text: str) -> None:
    """Write-temp-then-rename in the destination directory (same fs).

    The temp name is dot-prefixed AND ``.tmp``-suffixed so a temp left
    behind by a killed process can never match a consumer's artifact
    pattern (``*.trace.json`` etc.), glob dotfile semantics or not."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp_",
                               suffix=os.path.basename(path) + ".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def write_artifact(path: str, payload: Dict[str, Any],
                   host_id: Optional[int] = None,
                   kind: Optional[str] = None,
                   header_extra: Optional[Dict[str, Any]] = None,
                   indent: Optional[int] = 1) -> str:
    """Stamp ``payload`` with the provenance header (plus any
    ``header_extra`` fields, e.g. the tracer's run_name) and write it
    atomically (``indent=None``: on one line, for a table of many
    thousand rows). Returns ``path``."""
    body = dict(payload)
    if "header" not in body:
        header = artifact_header(host_id=host_id, kind=kind)
        header.update(header_extra or {})
        body["header"] = header
    atomic_write_text(path, json.dumps(body, indent=indent, default=_json_safe))
    return path


def _json_safe(o):
    """Best-effort JSON coercion for numpy scalars and odd leaves."""
    try:
        import numpy as np
        if isinstance(o, (np.integer,)):
            return int(o)
        if isinstance(o, (np.floating,)):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
    except Exception:
        pass
    return str(o)
