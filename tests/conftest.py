import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 in its own process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    import hypothesis  # noqa: F401
except ImportError:  # container has no hypothesis wheel — use the shim
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "_vendor"))

import jax

jax.config.update("jax_enable_x64", False)

# dist/slow markers are registered in pyproject.toml [tool.pytest.ini_options]
