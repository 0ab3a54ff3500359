"""benchmark/tests run on the CPU, apart from tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They check the yardstick itself (trace reduction, percentiles, schedule,
contract, FLOP functions) and rehearse every driver end to end at toy
width. Nothing here measures anything: a rehearsal's numbers are never
printed under a metric's name."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
