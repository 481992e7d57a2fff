"""Shared runner for the benches' --devices axis.

A device belongs to one process at a time: once a process has initialized
a JAX backend it holds the chip, and a child that asks for it then fails or
hangs.  So the axis runs in this process whenever its devices are (or, by
the forced virtual device count in ``XLA_FLAGS``, will be) visible here,
and a child is started only while this process has initialized no backend
— the benches call this first, before their own measurements.  The child
re-runs the calling script in its ``--sharded-only`` mode under a forced
``devices``-wide virtual host platform (which also keeps the CPU threads
of the single-device sections whole) and prints one ``RESULT <json>``
line.  Any failure raises: a missing axis is never a silent skip.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

_FORCE = "xla_force_host_platform_device_count"


def _backend_initialized() -> bool:
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _forced_devices() -> int:
    m = re.search(_FORCE + r"=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 0


def run_devices_axis(script_file: str, script_args: list[str], devices: int,
                     run_here):
    """The --devices axis payload: ``run_here()`` in this process when
    ``devices`` devices are visible here, else ``script_file --sharded-only
    *script_args`` in a child.  Raises when the axis cannot run."""
    if _backend_initialized() or _forced_devices() >= devices:
        import jax

        have = len(jax.devices())
        if have < devices:
            raise RuntimeError(
                f"the --devices {devices} axis needs {devices} devices; this "
                f"process holds a {jax.default_backend()} backend with "
                f"{have}, and a child started now could not take them")
        return run_here()
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --{_FORCE}={devices}").strip()
    r = subprocess.run([sys.executable, os.path.abspath(script_file),
                        "--sharded-only"] + script_args,
                       capture_output=True, text=True, env=env)
    print("\n".join(l for l in r.stdout.splitlines()
                    if not l.startswith("RESULT ")))
    if r.returncode != 0:
        raise RuntimeError(f"--devices {devices} axis failed "
                           f"(exit {r.returncode}):\n{r.stderr[-2000:]}")
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    if not lines:
        raise RuntimeError(f"--devices {devices} axis printed no RESULT line")
    return json.loads(lines[0][len("RESULT "):])
