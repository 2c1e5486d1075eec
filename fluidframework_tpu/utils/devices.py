"""Where JAX runs: the accelerator, or CPU emulation of it.

One chip belongs to one process at a time: a parent that has touched
JAX holds it, and a child that needs it then fails or hangs. So every
choice between running on the chip and emulating it is made HERE,
from the environment alone, without starting a backend:

- `cpu_pinned` — the environment pins JAX to the CPU (the tests'
  conftest, this sandbox). Multi-device code then runs on forced
  virtual host devices (``--xla_force_host_platform_device_count``),
  which only exist if the flag precedes the first jax import — hence
  the subprocess helpers below. Emulation exercises correctness
  (bit-identity across topologies), not throughput scaling.
- otherwise the host's accelerator is used in-process, and a run that
  finds fewer devices than it asked for fails; it never falls back to
  the CPU.

`cpu_child_env` is the one rule that pins a child process to the CPU,
`holds_accelerator` says whether this process already holds the chip,
`device_report` is what a process says about its device,
`use_compile_cache` places JAX's persistent compilation cache, and
`track_compiles` / `compile_seconds` count the seconds this process
spends in backend compiles.

No jax import at module level: scalar processes pay nothing.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List, Mapping, Optional

__all__ = [
    "COMPILE_CACHE_ENV",
    "compile_seconds",
    "cpu_child_env",
    "cpu_pinned",
    "device_report",
    "forced_host_device_env",
    "holds_accelerator",
    "run_forced_host_subprocess",
    "track_compiles",
    "use_compile_cache",
]

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def cpu_pinned(env: Optional[Mapping[str, str]] = None) -> bool:
    """True when `env` (default: os.environ) pins JAX to the CPU
    backend alone. Reads the environment only — no backend starts."""
    env = os.environ if env is None else env
    platforms = [p.strip()
                 for p in env.get("JAX_PLATFORMS", "").split(",")
                 if p.strip()]
    return bool(platforms) and all(p == "cpu" for p in platforms)


def cpu_child_env(base: Optional[Mapping[str, str]] = None
                  ) -> Dict[str, str]:
    """A child-process environment pinned to the CPU backend: the one
    rule every process that must not take the chip is spawned under."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def holds_accelerator() -> bool:
    """True when THIS process has started a non-CPU JAX backend — it
    then holds the chip, and a child that needs the chip would fail or
    hang. Never starts a backend itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() != "cpu")


def forced_host_device_env(n_devices: int,
                           base: Optional[Dict[str, str]] = None
                           ) -> Dict[str, str]:
    """A child-process environment with N virtual host CPU devices:
    os.environ (or `base`) with any previous force flag replaced and
    the platform pinned to cpu (the forced count exists only there)."""
    env = cpu_child_env(base)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(
        f"--xla_force_host_platform_device_count={int(n_devices)}"
    )
    env["XLA_FLAGS"] = " ".join(flags)
    return env


_DEVICE_REPORT: Optional[dict] = None


def device_report() -> dict:
    """This process's device as its heartbeat reports it:
    ``{"platform", "kind", "count"}`` of the default jax backend once
    the process has imported jax (which this then initializes), else
    the platform its environment pins (``kind`` None)."""
    global _DEVICE_REPORT
    if _DEVICE_REPORT is not None:
        return _DEVICE_REPORT
    jax = sys.modules.get("jax")
    if jax is None:
        return {"platform": os.environ.get("JAX_PLATFORMS") or None,
                "kind": None, "count": 0}
    devs = jax.devices()
    _DEVICE_REPORT = {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}
    return _DEVICE_REPORT


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here. Otherwise the cache lives at
    ``<repo root>/.jax_cache`` — a fixed absolute path derived from the
    package (the path is part of the cache key, so a directory that
    moved would never hit). The variable is exported too, so children
    spawned afterwards share the one cache."""
    path = os.environ.get(COMPILE_CACHE_ENV)
    if path:
        return path
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    os.environ[COMPILE_CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:  # imported already: the env var was read
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_COMPILE_S: Optional[List[float]] = None  # [total] once tracking


def track_compiles() -> None:
    """Start counting this process's backend-compile seconds (a
    persistent-cache hit skips the compile, so it adds nothing).
    Idempotent, and a no-op in a process that has not imported jax:
    scalar processes compile nothing and pay nothing."""
    global _COMPILE_S
    jax = sys.modules.get("jax")
    if _COMPILE_S is not None or jax is None:
        return

    _COMPILE_S = [0.0]

    def on_event(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_S[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)


def compile_seconds() -> Optional[float]:
    """Backend-compile seconds since `track_compiles`, else None."""
    return None if _COMPILE_S is None else _COMPILE_S[0]


def run_forced_host_subprocess(
    code: str, n_devices: int, timeout_s: float = 900.0,
    cwd: Optional[str] = None, argv: Optional[List[str]] = None,
) -> subprocess.CompletedProcess:
    """Run ``python -c code [argv...]`` under N forced virtual host
    devices (the flag must precede the first jax import, hence the
    subprocess) — CPU emulation only: on an accelerator host the work
    runs in-process. Raises RuntimeError with both streams on a
    non-zero exit — a silently failed emulation child must not look
    like an empty result."""
    res = subprocess.run(
        [sys.executable, "-c", code] + list(argv or []),
        env=forced_host_device_env(n_devices),
        capture_output=True, text=True, timeout=timeout_s, cwd=cwd,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"forced-host-device subprocess failed "
            f"(rc={res.returncode}, n_devices={n_devices})\n"
            f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
        )
    return res
