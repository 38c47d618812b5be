# JAX PE-array execution kernels (optional extra: pip install .[jax]).
#
#   ops       — jit'd instruction-grid runner (decode_fields / init_state /
#               run_program), the entry point simulate() uses
#   ref       — pure-jnp cycle step: the PE-array semantics
#
# Everything importing this package defers the jax import to first use so
# mapping-only flows (SAT mapper, DSE sweep, traced-kernel legalization and
# the map-only co-sim lane) run with zero optional extras.  Not to be
# confused with the *CIL kernel registry* (repro.cgra.registry), which
# names the loop workloads those flows operate on.

import os
from pathlib import Path

_SUBMODULES = ("ops", "ref")

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed directory in the checkout (the path is part of what a
# later run looks up, so it must not move between runs)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Let JAX keep compiled programs between runs.  JAX itself honours
    ``JAX_COMPILATION_CACHE_DIR``; only when that is unset does this point
    the cache at :data:`CACHE_DIR`.  Call it before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    try:
        import jax
    except ImportError:      # mapping-only install: nothing is compiled
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def __getattr__(name):
    if name in _SUBMODULES:
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
