"""paddle.nn.functional parity surface."""
from .activation import *  # noqa: F401,F403
# import the flash_attention SUBMODULE first: importing it later (e.g. via
# `from ...functional.flash_attention import flash_attn_unpadded`) would make
# importlib rebind the package attribute from the function to the module,
# breaking `F.flash_attention(q, k, v)` callers
from . import flash_attention as _flash_attention_module  # noqa: F401
from .attention import (  # noqa: F401
    asked_attn_kernel,
    decode_attention,
    flash_attention,
    paged_attention,
    prefill_attention,
    resolve_attn_kernel,
    scaled_dot_product_attention,
    sparse_attention,
)
from .flash_attention import flash_attn_unpadded  # noqa: F401
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import (  # noqa: F401
    batch_norm,
    group_norm,
    instance_norm,
    layer_norm,
    local_response_norm,
    normalize,
    rms_norm,
    spectral_norm_weight,
)
