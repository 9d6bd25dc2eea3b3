"""One import site for jax API spellings the package shares.

``typeof``: the aval of a value (carrying ``vma`` under shard_map).
``shard_map``: ``jax.shard_map`` with its ``check_vma=`` keyword.

Written against the installed jax (0.9.0); the rest of the package (and
the tests) import these names from here, so the next move of either API
is a one-line change.
"""

from __future__ import annotations

import jax

typeof = jax.typeof
shard_map = jax.shard_map
