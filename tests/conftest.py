"""Test harness: a fake 8-chip TPU cluster on CPU devices.

Mirrors the reference's multi-node-without-a-cluster strategy
(``python/ray/cluster_utils.py:135`` — multiple raylets as local processes):
here the stand-in for N TPU chips is N XLA host-platform devices
(``--xla_force_host_platform_device_count=8``), so every sharding/mesh test
runs the real pjit/shard_map code paths without TPU hardware.

The suite always runs on the CPU, whatever the machine holds: tests
check results and counts, never device speed, and a test process that
opened the chip would take it from whoever is measuring on it. The
platform is forced through ``jax.config`` (not only ``JAX_PLATFORMS``)
so that holds however pytest was launched; ``XLA_FLAGS`` must be set
before the CPU client first initializes, which is why it comes first.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402

import pytest  # noqa: E402

# Per-test hang guard, mirroring the reference's default 3-minute per-test
# timeout (``pytest.ini:15-16`` there). pytest-timeout isn't in the image, so
# a SIGALRM watchdog: CPython delivers signals on the main thread even while
# it is blocked on a lock acquire, so a deadlocked test fails loudly instead
# of wedging the whole suite. Override per-test with @pytest.mark.timeout(N).
_DEFAULT_TEST_TIMEOUT_S = 180


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test watchdog override"
    )


@pytest.fixture(autouse=True)
def _hang_guard(request):
    if not hasattr(signal, "SIGALRM"):  # non-POSIX fallback: no guard
        yield
        return
    marker = request.node.get_closest_marker("timeout")
    seconds = _DEFAULT_TEST_TIMEOUT_S
    if marker:
        if marker.args:
            seconds = int(marker.args[0])
        elif "seconds" in marker.kwargs:
            seconds = int(marker.kwargs["seconds"])

    def _on_timeout(signum, frame):
        raise TimeoutError(
            f"test exceeded {seconds}s watchdog (likely hang/deadlock)"
        )

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _fresh_config():
    """Each test sees a pristine config (env-derived)."""
    from ray_dynamic_batching_tpu.utils import config

    config.reset_config()
    yield
    config.reset_config()


@pytest.fixture
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 fake chips, got {len(devices)}"
    return devices[:8]
