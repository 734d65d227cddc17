"""One import site for ``shard_map`` and ``axis_size`` (jax 0.9)."""

from jax import shard_map  # noqa: F401
from jax.lax import axis_size  # noqa: F401
