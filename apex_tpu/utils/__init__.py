from apex_tpu.utils.logging import RankInfoFormatter, get_logger  # noqa: F401
from apex_tpu.utils.tree import (  # noqa: F401
    tree_cast,
    tree_global_norm,
    tree_isfinite,
    tree_size,
    tree_zeros_like,
)
from apex_tpu.utils.compile_cache import configure_compile_cache  # noqa: F401
