"""The delta rule's forward kernel: the share of its roofline — the least time
the chip could take for what the MODEL needs of the gated delta rule's forward
pass in the steps of the traced steady window (``flops/<config>.py``
``gated_delta_fwd_per_example``: the larger of operations over the bf16 peak and
bytes over the memory's rate; the products of ``T``, ``U`` and ``W``, which the
program makes in plain jax before the kernel, stay in that need), over the
device time of the operations named ``gated_delta_fwd*`` on the first chip's
``XLA Ops`` line in that window. None where no such operation ran (a program
without the kernel) or the run is of no cell on the metric's list."""

from harness import kernel_time

KERNEL = "gated_delta_fwd"     # the pallas_call's name: the operations'


def read(ctx: dict):
    return kernel_time.roofline_share(
        ctx, "gated_delta_fwd_roofline", KERNEL,
        "gated_delta_fwd_per_example")
