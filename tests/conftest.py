import os
import sys

# Tests run on the single real CPU device (the 512-device farm is strictly a
# dry-run affair, per the assignment). Model code takes the XLA GLA path on
# CPU; the Pallas kernels are exercised explicitly in test_kernels.py.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))
