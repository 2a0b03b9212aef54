"""Backend selection shared by every Pallas kernel wrapper.

The kernels run in two modes:

* ``interpret=True``  — Pallas interpreter; works on any backend (CPU CI).
* ``interpret=False`` — compiled Pallas; TPU backends only.

Every kernel entry point takes ``interpret: bool | None = None`` and
resolves ``None`` through :func:`default_interpret`: compiled on a real TPU
backend, interpreted elsewhere. The ``REPRO_KERNEL_INTERPRET`` environment
variable overrides auto-detection (``1``/``true``/``interpret`` forces the
interpreter, ``0``/``false``/``compiled`` forces compiled Pallas,
``auto``/unset keeps detection). Forcing the interpreter on a TPU backend
is an error, not a mode: served kernels there always compile.

Resolution scope: the top-level kernel entry points (``sbmm``,
``token_drop``, ``flash_attention``) resolve OUTSIDE their jits, so for
direct calls the resolved value is a static jit argument and flipping the
env var between calls re-dispatches. Kernel calls nested inside an outer
jitted program (``PackedVitSegments`` segments, ``ModelRunner`` steps)
resolve at *trace* time and the mode is baked into that trace — set the
env var before the first engine step (in practice: at process launch);
flipping it mid-engine does not retrace already-compiled steps.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "REPRO_KERNEL_INTERPRET"

_TRUE = ("1", "true", "yes", "on", "interpret")
_FALSE = ("0", "false", "no", "off", "compiled")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret on non-TPU backends unless the env var says otherwise.
    Raises if the env var forces the interpreter on a TPU backend."""
    env = os.environ.get(ENV_VAR, "auto").strip().lower()
    if env in _TRUE:
        if on_tpu():
            raise RuntimeError(
                f"{ENV_VAR}={env!r} would run the Pallas kernels in the "
                f"interpreter on a TPU backend; unset it to compile them")
        return True
    if env in _FALSE:
        return False
    if env not in ("", "auto"):
        raise ValueError(
            f"{ENV_VAR}={env!r}: expected one of {_TRUE + _FALSE} or 'auto'")
    return not on_tpu()


def resolve_interpret(interpret: "bool | None") -> bool:
    """``None`` -> auto-detected default; concrete bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)
