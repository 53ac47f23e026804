#!/usr/bin/env python3
"""Reckon a cell's bytes before it is asked for: compile its train step, and
its reference's gradient and update, for a described v5e chip in a sandbox
that has none, and print ``memory_analysis()`` of each and how many Pallas
kernels (``tpu_custom_call``) the step holds.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py <cell> [<cell> ...]
        [--config FILE] [--per-chip-batch N] [--undonated]

``--config`` puts a scratch configuration in the cell's place (a longer
``layers_kept``, say) and ``--per-chip-batch`` another batch, to find the room
for a cell that is not there yet. ``--undonated`` reckons the reference's
update as it was stepped before PR 33 (nothing donated: 7 copies of the
parameters under Adam), for the before-and-after table in PERF.md section 4.

Nothing runs: no time or rate comes from here (on-chip-measurement guide §2).
The step is built as ``fit`` builds it (``TrainState.create``,
``make_train_step`` over a one-axis data mesh), on shapes alone, and the
reference's two calls are ``harness.reference_run.jitted_calls``, the ones
``run_steps`` drives. While it lowers, ``"auto"`` attention is steered to the
chip's branch (``sparkdl_tpu.utils.platform.is_tpu_backend``): left alone, the
sandbox's JAX would trace the CPU's dense attention. A program the chip's
compiler refuses (too large for its memory) is printed as refused, with the
compiler's words.
"""

import argparse
import contextlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


@contextlib.contextmanager
def as_on_the_chip():
    """Code that asks the program which backend it is on gets the chip's
    answer while a step is lowered for a described chip."""
    from sparkdl_tpu.utils import platform
    real = platform.is_tpu_backend
    platform.is_tpu_backend = lambda: True
    try:
        yield
    finally:
        platform.is_tpu_backend = real


def analysis(what: str, lower) -> dict:
    """``memory_analysis()`` of ``lower()`` compiled, or the refusal."""
    try:
        compiled = lower().compile()
    except Exception as e:  # the compiler's refusal is the finding
        return {"what": what, "refused": str(e).strip().splitlines()[0][:400]}
    ma = compiled.memory_analysis()
    return {"what": what,
            "tpu_custom_call": compiled.as_text().count("tpu_custom_call"),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes}


def tree_bytes(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def reckon(name: str, topo, config=None, per_chip_batch=None,
           undonated: bool = False) -> list:
    """One dict per program of the cell: the step, the reference's gradient
    and the reference's update."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import loader
    from harness.reference_run import jitted_calls
    from sparkdl_tpu.runner.train_state import TrainState, make_train_step

    res = loader.resolve_cell(name)
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    if config:
        cfg = loader.load_json(config)
    if per_chip_batch:
        traffic = dict(traffic, per_chip_batch=per_chip_batch)
    ref = loader.load_module(*res["files"]["reference"])
    prog = loader.load_module(*res["files"]["program"])
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    weights = jax.eval_shape(lambda k: ref.init_weights(cfg, k),
                             jax.random.PRNGKey(0))
    kw = prog.fit_kwargs(cfg, weights)
    state = jax.eval_shape(lambda: TrainState.create(
        kw.get("apply_fn", lambda p, x: p), kw["params"], kw["tx"],
        model_state=kw.get("model_state")))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), state)
    rows = traffic["per_chip_batch"] * chips
    batch = {k: jax.ShapeDtypeStruct((rows, *v["shape"]),
                                     jnp.dtype(v["dtype"]), sharding=split)
             for k, v in traffic["inputs"].items()}
    step = make_train_step(kw["loss_fn"], mesh,
                           mutable=kw.get("mutable", False))
    with as_on_the_chip():
        out = [analysis("train step (program)",
                        lambda: step.lower(state, batch))]

    # the reference steps the global batch on one chip
    one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("d",)), P())

    def on_one(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = on_one(ref.trainable(weights))
    opt = on_one(jax.eval_shape(lambda p: ref.opt_init(cfg, p), params))
    grad, update = jitted_calls(ref, cfg)
    if undonated:
        update = jax.jit(update.__wrapped__)
    with jax.default_matmul_precision("highest"):
        g = analysis("reference gradient (float32, one chip)",
                     lambda: grad.lower(params, on_one(batch)))
        if "peak_estimate_bytes" in g:
            # the optimizer's state waits on the device while it runs
            g["resident_bytes"] = tree_bytes(opt)
            g["peak_estimate_bytes"] += g["resident_bytes"]
        u = analysis("reference update (%s)" % (
            "nothing donated" if undonated else
            "parameters and state donated"),
            lambda: update.lower(params, params, opt, on_one(
                jax.ShapeDtypeStruct((), jnp.float32))))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(params))
    return [dict(cell=name, parameters=n, rows=rows, **r)
            for r in (*out, g, u)]


def main(argv=None):
    import jax
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--config")
    ap.add_argument("--per-chip-batch", type=int)
    ap.add_argument("--undonated", action="store_true")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in args.cells:
        for rec in reckon(name, topo, args.config, args.per_chip_batch,
                          args.undonated):
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
