"""Pin this process's JAX runtime to the CPU with N virtual devices.

Two uses: the test suite, which must never reach for an accelerator, and
every multi-device surface (mesh sharding, mesh groups, the multichip dry
run), which a host with fewer real devices drives on virtual CPU devices.
`jax.config.update("jax_platforms", "cpu")` pins the platform (the config
route works after `import jax`, unlike the JAX_PLATFORMS variable, which
is read at import), and XLA_FLAGS provides the virtual device count,
which only takes effect when the CPU backend starts.

force_cpu() validates the result and raises CpuOnlyError loudly if a
non-CPU backend was already initialized (config changes cannot tear down
a live backend — call force_cpu before the first jax.devices()/jit)."""

from __future__ import annotations

import os


class CpuOnlyError(RuntimeError):
    """force_cpu() could not pin the runtime to CPU."""


def force_cpu(n_devices: int = 8) -> None:
    """Must run before the first jax.devices()/jit call in the process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()  # initializes the (cpu) backend eagerly
    if any(d.platform != "cpu" for d in devices):
        raise CpuOnlyError(
            f"force_cpu() ran too late: a non-CPU backend is already live "
            f"({sorted({d.platform for d in devices})}). Call force_cpu() "
            f"before anything touches jax.devices()/jit, or start the "
            f"process with JAX_PLATFORMS=cpu."
        )
    if len(devices) < n_devices:
        raise CpuOnlyError(
            f"force_cpu({n_devices}) got only {len(devices)} CPU devices — "
            f"XLA_FLAGS was applied after the CPU backend initialized. "
            f"Call force_cpu() earlier (before the first jax.devices()/jit)."
        )
