"""Backend identification — the single place that decides "are we on TPU?".

Three features key off the platform (flash-attention default, Pallas
interpret-mode auto-select, sharded decode-kernel dispatch). The check is
the backend JAX resolved, nothing else: a backend that fails to initialize
raises here instead of answering "not a TPU", because a False would
silently interpret every kernel and densify attention.
"""

from __future__ import annotations


def is_tpu_backend() -> bool:
    """True if jax's default backend is the TPU.

    Initializes the backend on first call (callers are all paths that are
    about to run on the backend anyway)."""
    import jax

    return jax.default_backend() == "tpu"


def backend_info() -> dict:
    """Observability record for the bench: what backend actually resolved."""
    import jax

    devs = jax.devices()
    return {"default_backend": jax.default_backend(),
            "n_devices": len(devs),
            "device_platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "is_tpu": is_tpu_backend()}
