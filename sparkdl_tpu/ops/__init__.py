"""Pallas TPU kernels for the hot ops (SURVEY.md §5.7, pallas guide)."""

import jax

from .flash_attention import RESIDUAL_NAMES as _FLASH
from .flash_attention import auto_attn_fn, flash_attention, resolve_attn_fn
from .gated_delta import RESIDUAL_NAMES as _GATED_DELTA
from .selective_scan import RESIDUAL_NAMES as _SELECTIVE_SCAN
from .ssd_scan import RESIDUAL_NAMES as _SSD_SCAN

# The ``nn.remat`` policy of every decoder layer that calls one of these
# kernels: what a forward kernel wrote (each module's ``RESIDUAL_NAMES``) is
# kept for the backward, everything else in the layer is recomputed. A layer
# keeps the residuals of whichever kernels it calls; outside a checkpoint
# that carries this policy the names are the identity.
SAVE_KERNEL_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *_FLASH, *_SELECTIVE_SCAN, *_SSD_SCAN, *_GATED_DELTA)

__all__ = ["flash_attention", "auto_attn_fn", "resolve_attn_fn",
           "SAVE_KERNEL_RESIDUALS"]

# flash_decode / paged_flash_decode import lazily at their call sites
# (models.llama) — importing them here would pull pallas.tpu into every
# `from sparkdl_tpu import ops` even on jax-free paths.
