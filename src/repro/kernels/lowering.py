"""Platform-aware interpret default shared by the RSS matmul families.

The Pallas kernels run compiled (Mosaic) on a TPU backend and through the
Pallas interpreter everywhere else, which is bit-identical but slow.  An
explicit bool still wins: the bit-identity tests exercise both lowerings.
Kernel vs jnp reference is chosen elsewhere, once, by
``compile_secure(use_kernel_dot=...)``.
"""

from __future__ import annotations

from typing import Optional

import jax

# Backends whose Pallas lowering we trust for these int8-limb kernels.
_COMPILED_BACKENDS = ("tpu",)


def default_interpret() -> bool:
    """True when the current backend needs the Pallas interpreter.

    TPU backends run the compiled Mosaic lowering; CPU (and any other host
    platform) falls back to ``interpret=True``."""
    return jax.default_backend() not in _COMPILED_BACKENDS


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret`` argument: None means platform default."""
    return default_interpret() if interpret is None else bool(interpret)
