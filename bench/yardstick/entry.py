"""What every entry point under ``bench/`` does before it imports JAX."""
import os
import sys

from .spec import ROOT


def prepare() -> None:
    """Keep JAX's persistent compile cache at ``<checkout>/.jax_cache``
    (a fixed path, so the next run finds it, and none outside the
    checkout), keep the TPU runtime's logs out of ``/tmp``, and put the
    program (``<checkout>/src``) on the import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
