import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

# the harness points JAX's persistent compile cache at the checkout; tests
# on the CPU neither read nor write it
jax.config.update("jax_enable_compilation_cache", False)
