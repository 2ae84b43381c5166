import os

# the benchmark's own tests run on the CPU; the chip runs are the
# benchmark itself
os.environ.setdefault("JAX_PLATFORMS", "cpu")
