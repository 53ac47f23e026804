"""The step's operations carry the program's names (ISSUE 38): scopes register
themselves where they are opened (``utils/scopes.py``), one classifier sorts
device time by them (``runner/analysis.py``), and ``fit`` leaves what it takes
to print the table of its step program: instruction name -> ``op_name``."""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.ops.flash_attention import flash_attention
from sparkdl_tpu.runner import XlaRunner, analysis
from sparkdl_tpu.utils import scopes

FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- utils/scopes.py ----------------------------------------------------------

def test_a_layer_registers_its_name_when_it_is_traced_not_when_it_runs():
    def f(x):
        with scopes.layer("scope_table_probe"):
            return x * 2.0

    assert "scope_table_probe" not in scopes.names()
    g = jax.jit(f)
    lowered = g.lower(jnp.ones((4,)))
    assert "scope_table_probe" in scopes.names()
    assert "scope_table_probe" in lowered.as_text(debug_info=True)
    before = scopes.names()
    g(jnp.ones((4,)))
    g(jnp.ones((4,)))
    assert scopes.names() == before
    assert isinstance(scopes.names(), frozenset)


def test_no_scope_is_opened_past_the_registry():
    """``jax.named_scope(`` is spelled in ``utils/scopes.py`` alone: a scope
    opened anywhere else would reach the trace and no table."""
    hits = []
    for base, _, files in os.walk(os.path.join(ROOT, "sparkdl_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(base, fn)
                with open(path) as f:
                    if "named_scope(" in f.read():
                        hits.append(os.path.relpath(path, ROOT))
    assert hits == [os.path.join("sparkdl_tpu", "utils", "scopes.py")]


def _lfm2():
    from sparkdl_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM
    return Lfm2ForCausalLM(Lfm2Config.tiny(), attn_fn=FLASH), {
        "embed_tokens", "short_conv", "moe_router", "moe_dispatch",
        "moe_experts", "moe_combine", "lm_head_loss", "flash_attention_fwd",
        "flash_attention_bwd"}


def _phi():
    from sparkdl_tpu.models.phi4flash import (Phi4FlashConfig,
                                              Phi4FlashForCausalLM)
    c = Phi4FlashConfig(vocab_size=96, hidden_size=32, intermediate_size=48,
                        num_attention_heads=4, num_key_value_heads=2,
                        num_hidden_layers=8, sliding_window=5,
                        mamba_d_state=4)
    return Phi4FlashForCausalLM(c, attn_fn=FLASH), {
        "embed_tokens", "mamba_in_proj", "mamba_conv", "mamba_out_proj",
        "diff_attention", "cross_attention", "gated_memory",
        "selective_scan_fwd",
        "selective_scan_bwd", "lm_head_loss", "flash_attention_fwd",
        "flash_attention_bwd"}


def _granite():
    from sparkdl_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                                   GraniteHybridConfig,
                                                   GraniteHybridForCausalLM)
    c = GraniteHybridConfig(
        vocab_size=96, hidden_size=32, shared_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2,
        layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA), mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8)
    return GraniteHybridForCausalLM(c, attn_fn=FLASH), {
        "embed_tokens", "mamba_in_proj", "mamba_conv", "mamba_gated_norm",
        "mamba_out_proj", "nope_attention", "ssd_scan_fwd", "ssd_scan_bwd",
        "lm_head_loss", "flash_attention_fwd", "flash_attention_bwd"}


@pytest.mark.parametrize("build", [_lfm2, _phi, _granite])
def test_every_scope_a_decoders_gradient_opens_is_registered(
        build, monkeypatch):
    """What the trace opened (a spy on ``scopes.layer``) is what
    ``scopes.names()`` holds and what the lowered program's locations carry:
    a model that opens a scope needs no list kept elsewhere."""
    model, expected = build()
    opened = set()
    real = scopes.layer

    def spy(name):
        opened.add(name)
        return real(name)

    monkeypatch.setattr(scopes, "layer", spy)
    ids = np.random.default_rng(0).integers(0, 96, (2, 24)).astype(np.int32)
    w = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    loss_fn = causal_lm_loss_fn()

    def grad(p):
        return jax.grad(lambda q: loss_fn(
            q, model.apply_with_counters, {"input_ids": ids})[0])(p)

    text = jax.jit(grad).lower(w).as_text(debug_info=True)
    assert opened >= expected
    assert opened <= scopes.names()
    for name in opened:
        assert re.search(r'[/"(]%s[/")]' % name, text), name


# -- runner/analysis.py: the classifier ------------------------------------------

NAMES = frozenset({"mamba_conv", "lm_head_loss", "moe_experts",
                   "optimizer_update", "flash_attention_bwd"})


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jit(main)/transpose(jvp(M))/layers_1/checkpoint/"
     "mamba_conv/mul", "mamba_conv"),
    # the innermost registered name wins over an outer one
    ("jit(step)/transpose(jvp(M))/layers_1/checkpoint/moe_experts/"
     "flash_attention_bwd/pallas_call", "flash_attention_bwd"),
    # a transform is written around the first scope opened under it
    ("jit(step)/jvp(lm_head_loss)/jit(take_along_axis)/gather",
     "lm_head_loss"),
    ("jit(step)/transpose(jvp(lm_head_loss))/dot_general:", "lm_head_loss"),
    # the primitive at the end is no scope, whatever its name
    ("jit(step)/jvp(M)/layers_0/mamba_conv", ""),
    ("jit(step)/jvp(M)/layers_0/feed_forward/w1/dot_general", ""),
    ("jit(step)/jit(main)/add", ""),
    ("", ""),
])
def test_named_scope_of_picks_the_innermost_registered_name(op_name, want):
    assert analysis.named_scope_of(op_name, NAMES) == want


def test_scope_seconds_by_name_is_self_time_under_the_innermost_name():
    ms = 1_000_000
    triples = [
        # a loop the compiler made, with no metadata, around its body
        ("", 0, 10 * ms),
        ("jit(s)/transpose(jvp(M))/layers_1/checkpoint/mamba_conv/mul:",
         1 * ms, 3 * ms),
        ("jit(s)/jvp(M)/layers_1/mlp/dot_general:", 4 * ms, 2 * ms),
        # a scoped loop and its body, itself under a deeper name
        ("jit(s)/jvp(M)/layers_2/moe_experts/while:", 10 * ms, 8 * ms),
        ("jit(s)/jvp(M)/layers_2/moe_experts/flash_attention_bwd/x:",
         12 * ms, 5 * ms),
        ("jit(s)/jit(main)/jit(_where)/select_n:", 18 * ms, 1 * ms),
        ("jit(s)/optimizer_update/mul:", 19 * ms, 1 * ms),
    ]
    rep = analysis.scope_seconds(triples, names=NAMES)
    assert rep["total_s"] == pytest.approx(0.020)
    assert rep["by_name"] == pytest.approx({
        "(unscoped)": 0.005 + 0.001, "mamba_conv": 0.003, "(module)": 0.002,
        "moe_experts": 0.003, "flash_attention_bwd": 0.005,
        "optimizer_update": 0.001})
    assert sum(rep["by_name"].values()) == pytest.approx(rep["total_s"])
    assert "by_name" not in analysis.scope_seconds(triples)
    # today's "unscoped" phase is the same operations
    assert rep["by_phase"]["unscoped"] == pytest.approx(
        rep["by_name"]["(unscoped)"])


def test_the_cli_prints_the_table_by_name(tmp_path, capsys):
    from jax.profiler import ProfileData
    text = """
    planes { name: "/device:TPU:0"
      lines { name: "XLA Ops" timestamp_ns: 100
        events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
        events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
        events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "fusion.1" stats {
        metadata_id: 1 str_value: "jit(f)/jvp(M)/l_0/mamba_conv/mul:" } } }
      event_metadata { key: 2 value { id: 2 name: "fusion.2" stats {
        metadata_id: 1 str_value: "jit(f)/transpose(jvp(M))/l_0/w/dot:" } } }
      event_metadata { key: 3 value { id: 3 name: "copy.3" } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }"""
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert analysis.main(["--profile", str(tmp_path), "--named", "--scopes",
                          "mamba_conv,lm_head_loss", "--json"]) == 0
    import json
    rep = json.loads(capsys.readouterr().out)
    assert rep["by_name"] == pytest.approx({
        "mamba_conv": 2e-6, "(module)": 3e-6, "(unscoped)": 1e-6})
    analysis.main(["--profile", str(tmp_path), "--scopes", "mamba_conv"])
    out = capsys.readouterr().out
    assert "mamba_conv" in out and "(unscoped)" in out
    assert "by_name" not in analysis.device_time_by_scope(str(tmp_path))


# -- hlo_scopes: the compiled program's own table ----------------------------------

def _two_scopes_a_loop_and_a_gradient():
    def loss(w, x):
        with scopes.layer("table_first"):
            h = jnp.tanh(x @ w)
        with scopes.layer("table_second"):
            h = jax.lax.fori_loop(0, 3, lambda i, c: jnp.sin(c) * 1.5 + i, h)
        return jnp.sum(h * h)

    f = jax.jit(jax.grad(loss))
    return f.lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile().as_text()


def test_hlo_scopes_names_every_instruction_of_every_computation():
    text = _two_scopes_a_loop_and_a_gradient()
    table = analysis.hlo_scopes(text)
    # counted another way: every line that assigns is an instruction
    assigned = [ln.split(" = ", 1)[0].split()[-1].lstrip("%")
                for ln in text.splitlines()
                if " = " in ln and not ln.startswith("HloModule")]
    assert len(assigned) == len(set(assigned)), "names repeat in a module"
    assert set(table) == set(assigned) and len(table) > 10
    computations = [ln for ln in text.splitlines() if ln.rstrip().endswith("{")
                    and " = " not in ln]
    assert len(computations) >= 3, "entry, a loop's body and its condition"
    # a nested body's instructions are there, under the scope they were
    # traced in; so is the loop itself
    body = re.search(r"\bwhile\(.*body=%?([\w.\-]+)", text).group(1)
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if re.match(r"\s*%?" + re.escape(body) + r"\s", ln))
    inner = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        inner.append(ln.split(" = ", 1)[0].split()[-1].lstrip("%"))
    assert inner and all(n in table for n in inner)
    names = {"table_first", "table_second"}
    assert any(analysis.named_scope_of(table[n], names) == "table_second"
               for n in inner)
    found = {analysis.named_scope_of(v, names) for v in table.values()}
    assert found >= names
    assert "" in table.values(), "an instruction without metadata maps to ''"


def test_hlo_scopes_reads_the_chips_way_of_writing_an_instruction():
    text = '''HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(128)} parameter(0)
  ROOT %multiply.3 = f32[8]{0:T(128)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(M)/l_0/mamba_conv/mul" source_file="m.py" source_line=7}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0:T(128)} parameter(0), metadata={op_name="state.params['w']"}
  %fusion.106 = f32[8]{0:T(128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(M)/l_0/mamba_conv/mul" source_file="m.py" source_line=7}
  %flash_attention_fwd.1 = f32[8]{0:T(128)} custom-call(%fusion.106), custom_call_target="tpu_custom_call", backend_config={"x": "a = b"}, metadata={op_name="jit(step)/jvp(M)/l_1/flash_attention_fwd/pallas_call"}
  %copy-start.5 = (f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, u32[]{:S(2)}) copy-start(%flash_attention_fwd.1)
  ROOT %all-reduce.416 = f32[8]{0:T(128)} all-reduce(%fusion.106), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/grad_allreduce/psum"}
}
'''
    assert analysis.hlo_scopes(text) == {
        "param_0.1": "",
        "multiply.3": "jit(step)/jvp(M)/l_0/mamba_conv/mul",
        "Arg_0.1": "state.params['w']",
        "fusion.106": "jit(step)/jvp(M)/l_0/mamba_conv/mul",
        "flash_attention_fwd.1":
            "jit(step)/jvp(M)/l_1/flash_attention_fwd/pallas_call",
        "copy-start.5": "",
        "all-reduce.416": "jit(step)/grad_allreduce/psum"}


# -- fit keeps what names its step ---------------------------------------------------

def _linear_apply(params, x):
    return x @ params["w"]


def _fit(width: int):
    from sparkdl_tpu.runner.train_state import softmax_cross_entropy_loss
    rng = np.random.RandomState(0)
    data = [{"image": rng.randn(16, 4).astype(np.float32),
             "label": rng.randint(0, width, (16,))} for _ in range(3)]
    params = {"w": rng.randn(4, width).astype(np.float32)}
    return XlaRunner(np=8).run(lambda ctx: ctx.fit(
        loss_fn=softmax_cross_entropy_loss(), params=params,
        tx=optax.sgd(0.1), apply_fn=_linear_apply, data=data, num_steps=3,
        resume=False))


def test_step_program_scopes_after_a_fit(monkeypatch):
    monkeypatch.setattr(analysis, "_STEP_PROGRAM", None)
    assert analysis.step_program_scopes() is None
    assert analysis.step_program_build_s() is None
    _fit(3)
    # nothing is built unless asked
    assert analysis._STEP_PROGRAM["table"] is None
    assert analysis.step_program_build_s() is None
    table = analysis.step_program_scopes()
    assert table and analysis.step_program_build_s() > 0
    assert analysis.step_program_scopes() is table, "built once, then kept"
    found = {analysis.named_scope_of(v, scopes.names())
             for v in table.values()}
    assert "optimizer_update" in found
    first = analysis._STEP_PROGRAM
    _fit(5)
    assert analysis._STEP_PROGRAM is not first, "replaced by the next fit"
    assert analysis._STEP_PROGRAM["table"] is None
    assert analysis.step_program_scopes() is not table


def test_a_step_that_is_no_jit_function_gives_no_table(monkeypatch):
    monkeypatch.setattr(analysis, "_STEP_PROGRAM", None)
    analysis.note_step_program(lambda: (lambda s, b: (s, {})),
                               jnp.ones((2,)), jnp.ones((2,)))
    assert analysis.step_program_scopes() is None
    # nor does a leaf that is no device array: never part of a table
    analysis.note_step_program(lambda: jax.jit(lambda s, b: s),
                               jnp.ones((2,)), 3)
    assert analysis.step_program_scopes() is None
    analysis.note_step_program(lambda: jax.jit(lambda s, b: s * b),
                               jnp.ones((2,)), jnp.ones((2,)))
    assert analysis.step_program_scopes()


def test_the_noted_slot_keeps_no_step_function_alive(monkeypatch):
    """A live jit function keeps its executable loaded (on a TPU, with the
    program's scratch reserved): ``fit`` leaves the recipe, not the
    function, and building the table leaves no function behind either."""
    import gc
    import weakref
    monkeypatch.setattr(analysis, "_STEP_PROGRAM", None)
    made = []

    def make_step():
        fn = jax.jit(lambda s, b: s * b)
        made.append(weakref.ref(fn))
        return fn

    analysis.note_step_program(make_step, jnp.ones((2,)), jnp.ones((2,)))
    assert not made, "nothing is made, traced or compiled at note time"
    assert analysis.step_program_scopes()
    gc.collect()
    assert len(made) == 1 and made[0]() is None
    _fit(3)
    slot = analysis._STEP_PROGRAM
    assert not any(hasattr(v, "lower") for v in slot.values())
