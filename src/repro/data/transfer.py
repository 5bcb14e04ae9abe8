"""Host↔device copies, each counted where it is made.

:func:`upload`, :func:`fetch` and :func:`fetch_tree` make the copy and,
while tracing records, add the bytes that crossed to a span's
``h2d_bytes`` or ``d2h_bytes``: the innermost open span (for
:func:`fetch_tree`, unless the caller names one).  Only a real crossing
counts: a jax array handed to :func:`upload`, or a host array handed to
:func:`fetch`, moves nothing.  A caller that keeps what it fetched
fetches it once (``StoredDataset.host_columns``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracer import current_span


def upload(x) -> jax.Array:
    """``jnp.asarray(x)``; a host array's bytes count as ``h2d_bytes``."""
    if isinstance(x, jax.Array):
        return x
    x = np.asarray(x)
    current_span().add("h2d_bytes", x.nbytes)
    return jnp.asarray(x)


def fetch(x) -> np.ndarray:
    """``np.asarray(x)``; a jax array's bytes count as ``d2h_bytes``."""
    if isinstance(x, jax.Array):
        current_span().add("d2h_bytes", x.nbytes)
    return np.asarray(x)


def fetch_tree(tree: Any, span=None) -> Any:
    """``jax.device_get(tree)``, one transfer for every leaf; the jax
    leaves' bytes count as ``d2h_bytes``."""
    sp = current_span() if span is None else span
    if sp.recording:
        sp.add("d2h_bytes", sum(
            int(a.nbytes) for a in jax.tree_util.tree_leaves(tree)
            if isinstance(a, jax.Array)))
    return jax.device_get(tree)
