"""The benchmark's own tests run on the CPU, with four host devices.

    python -m pytest bench/tests -q

Four devices let the mesh cell's path run at a small size. The harness's
compile cache is pointed at a temporary directory, never the checkout.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(_ROOT, "src"), _ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    import tempfile

    from bench import harness

    harness.CACHE_DIR = tempfile.mkdtemp(prefix="bench_test_cache_")
