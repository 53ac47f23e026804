"""Distributed data-parallel training — the HorovodRunner → XlaRunner
inversion (SURVEY.md §3.5 / BASELINE config 3).

The gradient allreduce is jax.lax.psum over the mesh's data axis, compiled
INTO the step function by XLA's SPMD partitioner — not a framework hook
outside the graph.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python examples/distributed_training.py
On a TPU slice, drop both env vars: the runner uses every local chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

import sparkdl_tpu as sdl
from sparkdl_tpu.models.registry import get_model
from sparkdl_tpu.runner import softmax_cross_entropy_loss


def main():
    steps = int(os.environ.get("STEPS", "6"))
    per_chip = int(os.environ.get("BATCH_PER_CHIP", "4"))

    runner = sdl.XlaRunner(np=-1)  # every visible device

    def train(ctx):
        import jax.numpy as jnp

        spec = get_model("ResNet18")
        model = spec.build(num_classes=10)
        variables = jax.tree_util.tree_map(np.asarray, model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False))

        def apply_fn(params, x):
            return model.apply(params, x, train=False)

        def data():
            rng = np.random.RandomState(0)
            n = per_chip * ctx.size
            while True:
                yield {"image": rng.randint(0, 256, (n, 32, 32, 3))
                       .astype(np.float32),
                       "label": rng.randint(0, 10, (n,))}

        return ctx.fit(loss_fn=softmax_cross_entropy_loss(),
                       params=variables, tx=optax.adam(1e-3),
                       apply_fn=apply_fn, data=data(), num_steps=steps,
                       log_every=max(1, steps // 3))

    res = runner.run(train)
    losses = [h["loss"] for h in res["history"]]
    print(f"{len(runner.devices)}-device DP: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {steps} steps")


if __name__ == "__main__":
    main()
