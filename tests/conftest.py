import os
import sys

# tests run single-device (the dry-run sets its own 512-device flag in a
# separate process); keep jax quiet and on CPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
