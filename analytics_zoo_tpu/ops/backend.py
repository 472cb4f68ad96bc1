"""Where the Pallas kernels of this package run.

On a TPU they compile through Mosaic. Everywhere else they run in the Pallas
interpreter, which is a correctness tool for tests and CPU rehearsals, not a
fast path. This is the one place that decides; kernels take ``interpret`` as
an argument so a test (or an ahead-of-time compile for a TPU topology from a
CPU host) can ask for the other mode explicitly.
"""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"
