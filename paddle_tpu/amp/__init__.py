from .auto_cast import (auto_cast, amp_guard, get_amp_state, AmpState,  # noqa: F401
                        white_list, black_list, decorate)
from .grad_scaler import GradScaler, AmpScaler  # noqa: F401
from . import debugging  # noqa: F401


def is_float16_supported(device=None) -> bool:
    """ref amp.is_float16_supported: TPUs compute natively in bf16; fp16
    works but without native matmul benefit."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def is_bfloat16_supported(device=None) -> bool:
    import jax
    return True  # bf16 is the native TPU compute dtype (CPU emulates)
