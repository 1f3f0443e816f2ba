"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so every multi-chip sharding
path (jax.sharding.Mesh over jobs/nodes axes) is exercised without TPU
hardware: ``JAX_PLATFORMS=cpu`` and the device-count flag are set here,
before the first jax import.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def forced_host_devices():
    """Eight forced-host CPU devices — the tier-1 mesh substrate.

    The module-level forcing above normally guarantees it; this fixture
    is the explicit dependency mesh test modules declare so that a run
    whose backend the forcing could NOT override (a TPU plugin that
    self-registered before conftest, a stripped-down CI worker) SKIPS
    the mesh set with an actionable reason instead of failing on an
    unrelated assertion.  Subprocess-isolated mesh work (the slow-tier
    scaling gate, bench_mesh.py) re-forces the same flags in its own
    process env, so it never depends on this process's backend at all.
    """
    if jax.default_backend() != "cpu" or len(jax.devices()) < 8:
        pytest.skip("needs 8 forced-host CPU devices "
                    "(xla_force_host_platform_device_count=8); this "
                    "process's backend was pinned before conftest could "
                    "force it — run via pytest from the repo root")
    return jax.devices()[:8]


def forced_cpu_env(n_devices: int = 8) -> dict:
    """Env for a subprocess that must see ``n_devices`` virtual CPU
    devices regardless of the parent's backend (the bench_mesh worker
    pattern): JAX_PLATFORMS pinned to cpu and any pre-existing
    device-count forcing replaced."""
    env = dict(os.environ)
    prior = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={n_devices}"] + prior)
    env["JAX_PLATFORMS"] = "cpu"
    return env
