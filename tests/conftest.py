import os
import sys

# Tests run single-device (the dry-run and the multi-device tests set
# their own device-count flags in subprocesses).  The one flag this process
# takes caps XLA's CPU code generation at AVX, the last x86 vector ISA
# without fused multiply-add: contracting ``a * b + c`` would leave the JAX
# scorer 1 ulp off the NumPy engine, whose bit parity the suite pins (see
# repro.core.jax_engine).
os.environ["XLA_FLAGS"] = "--xla_cpu_max_isa=AVX"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)
