from . import flash_attention  # noqa: F401
from . import paged_attention  # noqa: F401
from . import prefill_attention  # noqa: F401
