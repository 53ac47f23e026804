"""The chunked scan's forward kernel: the share of its roofline — the least
time the chip could take for what the MODEL needs of it in the steps of the
traced steady window (``flops/<config>.py`` ``ssd_scan_fwd_per_example``: the
larger of operations over the bf16 peak and bytes over the memory's rate), over
the device time of the operations named ``ssd_scan_fwd*`` on the first chip's
``XLA Ops`` line in that window. None where no such operation ran (a program
without the kernel) or the run is of no cell on the metric's list."""

from harness import kernel_time

KERNEL = "ssd_scan_fwd"     # the pallas_call's name: the operations'


def read(ctx: dict):
    return kernel_time.roofline_share(
        ctx, "ssd_scan_fwd_roofline", KERNEL, "ssd_scan_fwd_per_example")
