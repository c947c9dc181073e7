"""chip_smoke.py's four-chip phases on four virtual CPU devices, at the
tiny size of tests/test_chip_smoke.py. Run standalone:
PYTHONPATH=src python tests/_chip_smoke_check.py"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from repro.configs.base import GNNConfig  # noqa: E402
from test_chip_smoke import TINY, chip_smoke  # noqa: E402

chip_smoke.run_four_chips(GNNConfig().reduced(), TINY, n_dev=4)
print("ALL_OK")
