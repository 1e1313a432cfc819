import os

# The benchmark's tests run on the CPU; none touches an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import common  # noqa: E402

common.add_program_path()
