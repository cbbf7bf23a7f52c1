"""The CPU-proxy child: N virtual XLA host devices and no way to the chip.

``tools/chaos.py`` and ``__graft_entry__.dryrun_multichip`` need N
virtual host devices, which
must be configured before jax initializes, so each re-executes itself in a
child process.  A chip belongs to one process at a time and the parent may
be holding it: the child's environment therefore names the CPU outright
and inherits no TPU variable, so it can never reach for the parent's chip.
None of these launchers is on the default path of a chip command.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import Mapping, Optional, Sequence

#: prefixes of the variables that steer jax, PJRT or libtpu at a chip
_TPU_VARS = ("TPU_", "LIBTPU", "PJRT_", "CLOUD_TPU")
_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=(\d+)")
_CHILD_MARK = "_DL4J_CPU_PROXY_CHILD"


def cpu_proxy_env(devices: int,
                  base: Optional[Mapping[str, str]] = None) -> dict:
    """``base`` (default: this process's environment) made CPU-only with
    ``devices`` virtual host devices."""
    env = {k: v for k, v in (os.environ if base is None else base).items()
           if not k.startswith(_TPU_VARS) and k != "JAX_PLATFORM_NAME"}
    flags = _COUNT_FLAG.sub("", env.get("XLA_FLAGS", "")).split()
    flags.append(f"--xla_force_host_platform_device_count={int(devices)}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _already_proxy(devices: int) -> bool:
    """Started by hand with the proxy environment, jax not yet loaded."""
    m = _COUNT_FLAG.search(os.environ.get("XLA_FLAGS", ""))
    return (m is not None and int(m.group(1)) >= devices
            and os.environ.get("JAX_PLATFORMS") == "cpu"
            and "jax" not in sys.modules)


def reexec_on_cpu_proxy(devices: int, script: str,
                        argv: Sequence[str]) -> None:
    """Run ``script argv`` in a CPU-proxy child and exit with its code —
    unless this process already is that child, or was started with the
    proxy environment itself, in which case return and carry on."""
    if os.environ.get(_CHILD_MARK) == "1" or _already_proxy(devices):
        return
    env = cpu_proxy_env(devices)
    env[_CHILD_MARK] = "1"
    raise SystemExit(subprocess.call(
        [sys.executable, os.path.abspath(script), *argv], env=env))
