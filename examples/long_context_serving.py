"""Long-context serving: sequence-parallel prefill over a device mesh.

The prefill of a long prompt is O(S^2) attention compute — the part of
serving that actually needs more than one chip. Configuring the model's
``attn_fn`` with ring attention shards that compute over the ``sp`` mesh
axis (KV blocks hop the ICI ring via ``ppermute``) while the KV cache and
the per-token decode stay exactly as in single-chip serving. Tokens are
bit-identical to the dense single-device run — parallelism is layout,
not math.

On real hardware the mesh spans TPU chips; here the same code runs on a
virtual 8-device CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python examples/long_context_serving.py
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from sparkdl_tpu.core import runtime
from sparkdl_tpu.models.llama import LlamaConfig, LlamaModel, generate
from sparkdl_tpu.parallel.ring_attention import ring_attention


def main():
    n_dev = len(jax.devices())
    cfg = LlamaConfig.tiny()  # random init — swap in load_pretrained(...)
    dense = LlamaModel(cfg)

    # One knob turns on sequence parallelism: attn_fn=ring over an sp mesh.
    mesh = runtime.make_mesh({"sp": n_dev})
    sp_model = LlamaModel(cfg, attn_fn=functools.partial(
        ring_attention, mesh=mesh, axis="sp"))

    # "Long" prompt at example scale: S = 64 tokens = 8 tokens per device.
    # The same code serves 128k-token prompts on a real slice — S just has
    # to divide the sp axis.
    S, new = 64, 8
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(2, S)).astype(np.int32)
    variables = dense.init(jax.random.PRNGKey(0), ids[:1])

    ref = np.asarray(generate(dense, variables, ids, new))
    out = np.asarray(generate(sp_model, variables, ids, new))
    np.testing.assert_array_equal(out, ref)
    print(f"prefill of {S}-token prompts sharded over {n_dev} devices "
          f"({S // n_dev} tokens/device), decode unchanged")
    print("sequence-parallel tokens == single-device tokens, "
          "bit-identical.")


if __name__ == "__main__":
    main()
