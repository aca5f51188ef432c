# One rule for every kernel package: interpret vs compiled Pallas.
#
# All three kernel wrappers (`descent_score.ops`, `goldfinger_knn.ops`,
# `frh_minhash.ops`) resolve their `interpret=` argument through
# `interpret_mode()` at trace time, and the platform decides:
#
#   CPU backend  → interpret mode (the Pallas emulator, bitwise-checked
#                  against each package's `ref.py`)
#   TPU backend  → compiled Mosaic kernels
#   anything else → an error: the kernels are written for Mosaic, and a
#                  silent interpret-mode fallback on an accelerator would
#                  hide the chip behind the emulator.
#
# Tests can pin the mode with `set_interpret(True/False)`, which
# overrides the platform rule until `set_interpret(None)` restores it.

from __future__ import annotations

import jax

_override: bool | None = None


def set_interpret(value: bool | None) -> None:
    """Pin interpret mode (True/False), or None to follow the platform."""
    global _override
    _override = None if value is None else bool(value)


def interpret_mode() -> bool:
    """Resolve the interpret flag: override first, then the backend."""
    if _override is not None:
        return _override
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"default JAX backend is {backend!r}")
