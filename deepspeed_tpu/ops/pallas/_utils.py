"""Shared helpers for the Pallas kernel modules."""

from __future__ import annotations

from typing import Optional, Set, Tuple

import numpy as np

from ...utils.logging import logger
from ...utils.platform import on_chip


class KernelUnsupported(ValueError):
    """A Pallas kernel was asked for by name at a shape its gate refuses.
    Calling a kernel entry point (or configuring ``attention_impl="pallas"``,
    ``decode_impl="pallas"``, ``megakernel=True``) IS asking by name: the
    caller gets this error with the shape and the reason, never a quiet
    XLA reference under a configuration that says Pallas. Callers that may
    take either path ask the kernel's ``*_refusal`` gate first and log
    their choice with :func:`log_path_once`."""


def refuse(kernel: str, shape, reason: str):
    raise KernelUnsupported(f"{kernel} refused for {shape}: {reason}")


def interpret_mode() -> bool:
    """Pallas interpreter on the CPU test mesh; Mosaic on the chip."""
    return not on_chip()


_logged_paths: Set[Tuple[str, str, str]] = set()


def log_path_once(kernel: str, path: str, why: str) -> None:
    """One log line per distinct (kernel, path, reason): which
    implementation an ``"auto"`` choice took and why."""
    key = (kernel, path, why)
    if key not in _logged_paths:
        _logged_paths.add(key)
        logger.info(f"{kernel}: taking the {path} path ({why})")


def auto_path(kernel: str, refusal: Optional[str]) -> bool:
    """Resolve an ``"auto"`` implementation choice: Pallas on the chip when
    the kernel's gate accepts the shape (``refusal is None``), the XLA
    reference otherwise. Logs the choice once."""
    if not on_chip():
        log_path_once(kernel, "xla", "cpu backend: Pallas would run in the "
                      "interpreter")
        return False
    if refusal is not None:
        log_path_once(kernel, "xla", f"kernel gate refused: {refusal}")
        return False
    log_path_once(kernel, "pallas", "tpu backend, gate accepts the shape")
    return True


def rows_block(n_rows: int, max_block: int = 256) -> int:
    """Largest power-of-two row-block <= max_block dividing n_rows.
    Returns 0 when no block >= 8 divides (TPU Mosaic needs the
    second-to-last block dim to be a multiple of the 8-row sublane tile or
    equal to the array dim)."""
    cand = max_block
    while cand >= 8:
        if n_rows % cand == 0:
            return cand
        cand //= 2
    return n_rows if n_rows < 8 else 0


def require_rows(kernel: str, shape, max_block: int = 256) -> None:
    """Gate shared by the row-parallel kernels (layer_norm, softmax,
    bias_gelu) over [..., D] inputs: raise where no row block tiles."""
    n_rows = int(np.prod(shape[:-1]))
    if rows_block(n_rows, max_block) == 0:
        refuse(kernel, shape, f"{n_rows} rows: no power-of-two row block "
               f"in [8, {max_block}] divides the row count")
