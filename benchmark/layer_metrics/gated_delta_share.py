"""Share (%) of the traced steady window that the first chip spends in the
operations named ``gated_delta*`` on its ``XLA Ops`` line: the delta rule's
recurrence across chunks, forward (once a step: the layers' remat keeps what
it wrote) and backward. What is made in plain jax before the kernels reads
under ``gated_delta_prep_share``. None where no such operation ran."""

from harness import kernel_time


def read(ctx: dict):
    got = kernel_time.window_seconds(ctx, kernel_time.named("gated_delta"))
    if got is None or got[0] <= 0:
        return None
    return 100.0 * got[0] / got[1]
