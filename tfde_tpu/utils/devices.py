"""Virtual CPU device setup (the CPU test method: N devices on one host)."""

from __future__ import annotations


def request_cpu_devices(n: int) -> None:
    """Ask for `n` virtual CPU devices on the host platform.

    Must run before the JAX backend initializes (i.e. before the first
    device/computation touch; importing jax is fine) — jax raises if the
    backend is already up.
    """
    import jax

    jax.config.update("jax_num_cpu_devices", int(n))
