import os
import sys

# tests run against the source tree regardless of install state
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", False)

# The suite is compile-bound on CPU; a persistent compilation cache makes
# warm reruns several times faster (cold runs are unaffected).
enable_compile_cache()
