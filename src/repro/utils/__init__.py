from repro.utils.pytree import (
    param_count,
    param_bytes,
    tree_flatten_with_names,
    global_norm,
    tree_zeros_like,
    tree_cast,
)
from repro.utils.prng import PRNGSeq

__all__ = [
    "param_count",
    "param_bytes",
    "tree_flatten_with_names",
    "global_norm",
    "tree_zeros_like",
    "tree_cast",
    "PRNGSeq",
]
