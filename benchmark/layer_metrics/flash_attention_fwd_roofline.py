"""The flash-attention forward kernel's share of its roofline: the least time
the chip could take for the work the MODEL needs of it in the steps of the
traced steady window (``flops/<config>.py``: the causal pairs' two products,
and the bytes of q, the output and each key/value head once; the larger of
operations over the bf16 peak and bytes over the memory's rate), over the
device time of the operations named ``flash_attention_fwd`` on the first
chip's ``XLA Ops`` line in that window. A step that recomputes its layers
runs the kernel twice for one need, so the share cannot pass 50 there."""

from harness import kernel_time, trace as trace_lib

KERNEL = "flash_attention_fwd"     # the pallas_call's name: the operations'


def read(ctx: dict):
    # the operation's own name, not its HLO line: a consumer's line names
    # the kernel among its operands
    got = kernel_time.window_seconds(
        ctx, lambda name: trace_lib.short_name(name).startswith(KERNEL))
    if got is None or got[0] <= 0:
        return None
    cell = kernel_time.cell_of("flash_attention_fwd_roofline", ctx)
    if cell is None:
        return None
    need = cell["flops_module"].flash_attention_fwd_per_example(
        cell["config"], cell["traffic"])
    examples = ctx["global_batch"] / ctx["chips"] * got[2]
    least = max(need["flops"] / ctx["peak"]["bf16_flops_per_s"],
                need["bytes"] / ctx["peak"]["hbm_bytes_per_s"]) * examples
    return 100.0 * least / got[0]
