"""The one interpret-mode switch shared by every kernel's ops layer.

``INTERPRET`` is True when the process is not on the TPU backend: CPU
test runs then execute the kernels through the Pallas interpreter.  On the
chip it is False and every kernel lowers to a ``tpu_custom_call``;
``chip_smoke.py`` refuses to run unless that holds.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas.tpu import CompilerParams

INTERPRET = jax.default_backend() != "tpu"

__all__ = ["INTERPRET", "CompilerParams"]
