#!/usr/bin/env python3
"""Compile a cell's train step, and its reference's gradient, for a described
v5e chip in a sandbox that has none, and print ``memory_analysis()`` and
whether the lowered step holds a Pallas kernel (``tpu_custom_call``).

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py <cell> [<cell> ...]

Nothing runs: no time or rate comes from here (on-chip-measurement guide §2).
The step is built as ``fit`` builds it (``TrainState.create``,
``make_train_step`` over a one-axis data mesh), on shapes alone.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def main(cells):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import loader
    from sparkdl_tpu.runner.train_state import TrainState, make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in cells:
        res = loader.resolve_cell(name)
        cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
        ref = loader.load_module("references", res["cell"]["config"])
        prog = loader.load_module("programs", res["cell"]["config"])
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        weights = jax.eval_shape(lambda k: ref.init_weights(cfg, k),
                                 jax.random.PRNGKey(0))
        kw = prog.fit_kwargs(cfg, weights)
        state = jax.eval_shape(lambda: TrainState.create(
            lambda p, x: p, kw["params"], kw["tx"],
            model_state=kw.get("model_state")))
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            state)
        rows = traffic["per_chip_batch"] * chips
        batch = {k: jax.ShapeDtypeStruct((rows, *v["shape"]),
                                         jnp.dtype(v["dtype"]), sharding=split)
                 for k, v in traffic["inputs"].items()}
        step = make_train_step(kw["loss_fn"], mesh,
                               mutable=kw.get("mutable", False))
        lowered = step.lower(state, batch)
        compiled = lowered.compile()
        text = compiled.as_text()
        ma = compiled.memory_analysis()
        out = {"cell": name, "what": "train step (program)",
               "tpu_custom_call": text.count("tpu_custom_call"),
               "argument_bytes": ma.argument_size_in_bytes,
               "output_bytes": ma.output_size_in_bytes,
               "alias_bytes": ma.alias_size_in_bytes,
               "temp_bytes": ma.temp_size_in_bytes,
               "peak_estimate_bytes": ma.argument_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes
               + ma.temp_size_in_bytes}
        print(json.dumps(out), flush=True)
        one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("d",)), P())
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            ref.trainable(weights))
        rbatch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                  for k, v in batch.items()}
        with jax.default_matmul_precision("highest"):
            g = jax.jit(jax.value_and_grad(
                lambda p, b: ref.loss_fn(cfg, p, b, "float32")))
            ma = g.lower(params, rbatch).compile().memory_analysis()
        print(json.dumps({
            "cell": name, "what": "reference gradient (float32, one chip)",
            "temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
