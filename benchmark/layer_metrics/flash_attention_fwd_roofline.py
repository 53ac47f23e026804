"""The flash-attention forward kernel's share of its roofline: the least time
the chip could take for the work the MODEL needs of it in the steps of the
traced steady window (``flops/<config>.py``: the causal pairs' two products,
and the bytes of q, the output and each key/value head once; the larger of
operations over the bf16 peak and bytes over the memory's rate), over the
device time of the operations named ``flash_attention_fwd`` on the first
chip's ``XLA Ops`` line in that window. A step that recomputes its layers
runs the kernel twice for one need, so the share cannot pass 50 there."""

from harness import kernel_time

KERNEL = "flash_attention_fwd"     # the pallas_call's name: the operations'


def read(ctx: dict):
    return kernel_time.roofline_share(
        ctx, "flash_attention_fwd_roofline", KERNEL,
        "flash_attention_fwd_per_example")
