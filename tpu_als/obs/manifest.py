"""Run manifest: what produced this run dir — config, versions, git.

The reference stack records this in the Spark event log's
``SparkListenerEnvironmentUpdate`` / application properties; here it is
one JSON file next to the metrics, captured at ``obs.configure`` time
(cheap fields only) and completed at finalize (device info, which does
not exist until a backend initializes — configure must not be what
initializes it).
"""

from __future__ import annotations

import os
import platform
import subprocess
import sys
import time


def _git_describe():
    """``git describe --always --dirty`` of the source tree, or None —
    never raises (a deployed wheel has no .git)."""
    try:
        p = subprocess.run(
            ["git", "describe", "--always", "--dirty", "--tags"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        if p.returncode == 0:
            return p.stdout.strip()
    except Exception:
        pass
    return None


def build_manifest(config=None, argv=None):
    import numpy as np

    import tpu_als

    man = {
        "started_at": round(time.time(), 6),
        "argv": list(argv) if argv is not None else sys.argv[1:],
        "config": dict(config or {}),
        "tpu_als_version": tpu_als.__version__,
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "numpy": np.__version__,
        "git": _git_describe(),
        "pid": os.getpid(),
    }
    jax = sys.modules.get("jax")
    if jax is not None:
        man["jax"] = getattr(jax, "__version__", None)
    return man


def late_device_info():
    """Device/mesh facts gathered at FINALIZE time, when the backend has
    already initialized (or never will): jax.devices() here cannot add a
    hang the run didn't already have."""
    info = {}
    jax = sys.modules.get("jax")
    if jax is None:
        return info
    info["jax"] = getattr(jax, "__version__", None)
    try:
        devs = jax.devices()
        info["device_count"] = len(devs)
        info["device_kind"] = devs[0].device_kind if devs else None
        info["process_count"] = jax.process_count()
    except Exception:
        pass
    return info
