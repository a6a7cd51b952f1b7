"""Shared Pallas runtime configuration for the kernels package.

Every kernel wrapper in this package takes ``interpret: bool | None``;
``None`` resolves here so the whole package follows one policy:

* unset / ``auto`` — interpret off on a real TPU, on everywhere else;
* ``REPRO_PALLAS_INTERPRET=1`` (or ``true``/``on``/``yes``) — force
  interpret mode off the TPU (CPU correctness runs, CI). On a TPU it is an
  error: a kernel that should run compiled must not fall back to the
  interpreter unnoticed, so interpret mode there takes an explicit
  ``interpret=True`` argument;
* ``REPRO_PALLAS_INTERPRET=0`` (``false``/``off``/``no``) — force compiled
  kernels (only meaningful on a real TPU backend).

The value is read at trace time: jitted wrappers cache on the *resolved*
``interpret`` only through their first trace with ``interpret=None``, so
set the variable before the first kernel call (conftest/CI do).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "REPRO_PALLAS_INTERPRET"

_TRUE = ("1", "true", "on", "yes")
_FALSE = ("0", "false", "off", "no")


def default_interpret() -> bool:
    """Resolve the package-wide interpret default (see module docstring)."""
    v = os.environ.get(ENV_VAR, "auto").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if v in _TRUE:
        if on_tpu:
            raise RuntimeError(
                f"{ENV_VAR}={v} would run the Pallas kernels in interpret mode "
                "on a TPU; pass interpret=True explicitly where that is meant"
            )
        return True
    if v in _FALSE:
        return False
    return not on_tpu


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> :func:`default_interpret`, else the explicit value."""
    return default_interpret() if interpret is None else bool(interpret)


def row_tiling(nrows: int, row_len: int, target: int = 1 << 17) -> tuple[int, int]:
    """(rows per grid step, padded row count) for a ``[nrows, row_len]`` view.

    A short array is one whole-array block; a longer one takes steps of a
    multiple of 16 rows (the bf16 sublane tile, so every dtype here tiles)
    of about ``target`` elements, and is padded to whole steps.
    """
    rows = max(16, target // row_len // 16 * 16)
    if nrows <= rows:
        return nrows, nrows
    return rows, -(-nrows // rows) * rows
