"""Pallas flagship kernels for the paper's memory-intensive patterns."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter.

    The one place this is decided, from the backend: compiled Mosaic
    kernels on a TPU, the interpreter everywhere else (the CPU test
    host).  Every ``pallas_call`` in the repository asks here at trace
    time.
    """
    return jax.default_backend() != "tpu"


from . import ops, ref  # noqa: E402 - submodules call interpret_mode()
from .ops import attention, decode_attention, layernorm, rmsnorm, softmax, ssd_scan  # noqa: E402

__all__ = ["interpret_mode", "ops", "ref", "attention", "decode_attention",
           "layernorm", "rmsnorm", "softmax", "ssd_scan"]
