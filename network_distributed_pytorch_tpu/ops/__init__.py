"""TPU ops: Gram-Schmidt orthogonalization (XLA fori_loop + Pallas variants),
Pallas flash attention, the Pallas grouped matmul and the Pallas add of rows
into their tokens."""

from ._backend import pallas_interpret  # noqa: F401
from .orthogonalize import orthogonalize  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .grouped_matmul import grouped_matmul  # noqa: F401
from .rows_to_tokens import rows_of_tokens, tokens_from_rows  # noqa: F401
from .paged import (  # noqa: F401
    copy_block,
    gather_block_view,
    pool_chain_view,
    scatter_chain,
    scatter_token_rows,
)
