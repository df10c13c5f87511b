"""The one place this package adapts to the installed jax release.

Modules that need 64-bit integers or floats inside a jitted stage take
the scoped switch from here instead of naming jax's spelling of it, so
a jax upgrade that moves the name again is a one-line change.
"""

from __future__ import annotations

import jax


def enable_x64():
    """Context manager: 64-bit jax types inside the ``with`` block only
    (jax's global 32-bit default is left as it was)."""
    return jax.enable_x64(True)
