# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
from __future__ import annotations

import os


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Platform-aware default for the Pallas ``interpret`` flag.

    On a TPU backend ``None`` always resolves to the compiled kernel: no
    environment variable can send a TPU process to the interpreter.
    Off-TPU, ``None`` resolves to the interpreter, and
    ``REPRO_PALLAS_INTERPRET=1|0`` overrides that for the CPU tests. An
    explicit bool always wins (the compile tests pass ``False`` to build
    the TPU kernel from a CPU process).
    """
    if interpret is not None:
        return bool(interpret)
    if on_tpu():
        return False
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.lower() not in ("0", "false", "")
    return True
