"""Pallas kernels + the backend auto-detection the whole package shares.

Every kernel in this package has two execution modes: compiled Pallas (TPU
Mosaic) and ``interpret=True`` (the kernel body runs as traced jax ops, so
the same code validates on CPU CI hosts). :func:`kernel_backend` picks the
right one for the current platform — compiled on TPU, interpret elsewhere.
The ``REPRO_KERNEL_BACKEND`` environment variable (``pallas`` | ``interpret``
| ``auto``) can force compiled Pallas anywhere, and the interpreter only off
a TPU: on a TPU it raises rather than hide the device. All five kernels
(``streaming_matmul``, ``flash_attention``, ``ssd_scan``,
``decode_attention``, ``moe_experts``) and the tests resolve their
``interpret=None`` default through :func:`resolve_interpret`, so there is
exactly one place where the platform decision lives.
"""
from __future__ import annotations

import os

import jax

#: Environment override for the kernel execution mode.
BACKEND_ENV = "REPRO_KERNEL_BACKEND"

_VALID_BACKENDS = ("auto", "pallas", "interpret")


def kernel_backend() -> str:
    """``"pallas"`` (compiled Mosaic) or ``"interpret"``.

    Resolution order: the ``REPRO_KERNEL_BACKEND`` env var if set (``auto``
    defers), else compiled Pallas exactly when the default jax backend is a
    TPU. Raises :class:`ValueError` for an unknown override value so typos
    fail loudly instead of silently falling back to a 100x slower mode, and
    :class:`RuntimeError` for ``interpret`` on a TPU, where it would measure
    the interpreter in place of the chip.
    """
    choice = os.environ.get(BACKEND_ENV, "auto").strip().lower()
    if choice not in _VALID_BACKENDS:
        raise ValueError(
            f"{BACKEND_ENV}={choice!r}: expected one of {_VALID_BACKENDS}"
        )
    on_tpu = jax.default_backend() == "tpu"
    if choice == "interpret" and on_tpu:
        raise RuntimeError(
            f"{BACKEND_ENV}=interpret on a TPU: the kernels run compiled "
            "there; unset it or pass interpret=True to one call explicitly"
        )
    if choice != "auto":
        return choice
    return "pallas" if on_tpu else "interpret"


def resolve_interpret(interpret: bool | None) -> bool:
    """Map a kernel's ``interpret`` argument (``None`` = auto) to a bool."""
    if interpret is None:
        return kernel_backend() == "interpret"
    return bool(interpret)
