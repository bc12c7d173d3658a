import os

# Tests run on the single host CPU device. Keep XLA quiet and
# single-threaded-friendly.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
