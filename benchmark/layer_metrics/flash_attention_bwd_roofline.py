"""The flash-attention backward kernel pair's share of its roofline: the
least time for the work the MODEL needs of the backward pass of attention in
the steps of the traced steady window (``flops/<config>.py``: five products
over the causal pairs; q, k, v, o, do in and dq, dk, dv out once, the row
statistics), over the device time of the operations named
``flash_attention_bwd*`` (``_dkv`` and ``_dq``) on the first chip's ``XLA
Ops`` line in that window. The pair does seven products for the five (each
kernel recomputes the scores), so the share cannot pass 5/7 of the peak's."""

from harness import kernel_time

KERNELS = "flash_attention_bwd"    # both pallas_calls' names start so


def read(ctx: dict):
    return kernel_time.roofline_share(
        ctx, "flash_attention_bwd_roofline", KERNELS,
        "flash_attention_bwd_per_example")
