"""The benchmark's by-scope readers (ISSUE 38): ``benchmark/harness/
scope_time.py`` joins the driver's trace to the program's table of its step
by operation name, and seven readers under ``benchmark/layer_metrics/`` sum
the program's scopes. A join that fails reads None, never a number."""

import os
import sys

import pytest

from sparkdl_tpu.runner import analysis
from sparkdl_tpu.utils import scopes

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader, scope_time  # noqa: E402

LFM2 = "lfm2-8b-a1b.pretrain-s8192-b2"
PHI = "phi-4-mini-flash.sft-s8192-b1"
GRANITE = "granite-4.0-h-micro.sft-s8192-b1"
QWEN = "qwen3-next-80b-a3b.sft-s8192-b1"     # PR 40: appended to the lists
ST = "smallthinker-21b-a3b.sft-s16384-b1"    # appended after QWEN
EVERY = tuple(w["name"] for w in loader.load_benchmark()["workloads"])
# metric -> (the scopes it sums, the cells it is read in, its layer)
READERS = {
    "unscoped_share": (("(unscoped)",), EVERY, "train step"),
    "lm_head_loss_share": (("lm_head_loss",),
                           (LFM2, PHI, GRANITE, QWEN, ST), "head and loss"),
    "moe_dispatch_combine_share": (
        ("moe_router", "moe_dispatch", "moe_combine"), (LFM2, QWEN, ST),
        "expert layer"),
    "moe_experts_share": (("moe_experts",), (LFM2, QWEN, ST),
                          "expert layer"),
    "short_conv_share": (("short_conv",), (LFM2,), "short convolution"),
    "mamba_proj_share": (("mamba_in_proj", "mamba_out_proj"),
                         (PHI, GRANITE, QWEN), "state-space layer"),
    "mamba_conv_share": (("mamba_conv",), (PHI, GRANITE, QWEN),
                         "state-space layer"),
}
# PR 40's by-scope readers, appended behind PR 38's seven
LATER = {
    "gated_delta_prep_share": (("gated_delta_prep", "gdn_gated_norm"),
                               (QWEN,), "linear-attention layer"),
    "moe_router_share": (("moe_router",), (QWEN, LFM2, ST), "expert layer"),
    # the SmallThinker cell's two, appended behind them
    "attn_window_share": (("attn_window",), (ST,), "attention layer"),
    "attn_global_share": (("attn_global",), (ST,), "attention layer"),
}
MS = 1e6
STEP = 100 * MS     # one step program every 100 ms; the window holds 3
TABLE = {
    "fusion.1": "jit(step)/jvp(M)/l_0/mamba_in_proj/dot_general",
    "fusion.2": "jit(step)/transpose(jvp(M))/l_0/checkpoint/mamba_out_proj/"
                "dot_general",
    "fusion.3": "jit(step)/jvp(M)/l_0/mamba_conv/mul",
    "fusion.4": "jit(step)/jvp(lm_head_loss)/dot_general",
    "fusion.5": "jit(step)/jvp(M)/l_0/mlp/dot_general",
    "while.3": "",
    "fusion.6": "jit(step)/jvp(M)/l_1/moe_router/top_k",
    "copy.7": "",
}
NAMES = frozenset({"mamba_in_proj", "mamba_out_proj", "mamba_conv",
                   "lm_head_loss", "moe_router"})


def _ops(t0: float, stray_ms: float = 0.0) -> list:
    """One step's operations from ``t0``, named as a trace names them (the
    whole HLO line): 100 ms busy, of which ``stray_ms`` in an operation the
    table does not know."""
    def ev(name, at_ms, dur_ms):
        return ("%%%s = f32[8]{0} fusion(%%p.1, %%fusion.1)" % name,
                t0 + at_ms * MS, dur_ms * MS)
    return [ev("fusion.1", 0, 20), ev("fusion.2", 20, 10),
            ev("fusion.3", 30, 5), ev("fusion.4", 35, 15),
            ev("fusion.5", 50, 20 - stray_ms),
            ev("not_in_the_table.9", 70 - stray_ms, stray_ms),
            ev("while.3", 70, 25),          # a loop around its body:
            ev("fusion.6", 75, 10),         # 15 ms of it are its own
            ev("copy.7", 95, 5)]


def _ctx(stray_ms: float = 0.0) -> dict:
    steps = [i * STEP for i in range(5)]
    plane = {"XLA Modules": [("jit_step", t, STEP - 1) for t in steps],
             "XLA Ops": [e for t in steps for e in _ops(t, stray_ms)
                         if e[2] > 0]}
    return {"trace": {"/device:TPU:0": plane, "/host:CPU": {}}}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(analysis, "step_program_scopes", lambda: TABLE)
    monkeypatch.setattr(scopes, "names", lambda: NAMES)


def _read(metric: str, ctx: dict):
    return loader.load_module("layer_metrics", metric).read(ctx)


def test_a_synthetic_trace_and_table_give_the_hand_sums(table, capsys):
    ctx = _ctx()
    by_name, window_s = scope_time.seconds_by_name(ctx)
    assert window_s == pytest.approx(0.3)      # second step to the last
    assert by_name == pytest.approx({
        "mamba_in_proj": 0.060, "mamba_out_proj": 0.030, "mamba_conv": 0.015,
        "lm_head_loss": 0.045, "(module)": 0.060, "moe_router": 0.030,
        "(unscoped)": 0.045 + 0.015})
    assert _read("mamba_proj_share", ctx) == pytest.approx(30.0)
    assert _read("mamba_conv_share", ctx) == pytest.approx(5.0)
    assert _read("lm_head_loss_share", ctx) == pytest.approx(15.0)
    assert _read("moe_dispatch_combine_share", ctx) == pytest.approx(10.0)
    # the loop's self time and the copy, not the loop's whole 25 ms
    assert _read("unscoped_share", ctx) == pytest.approx(20.0)
    # no operation under these names ran: nothing to report
    assert _read("moe_experts_share", ctx) is None
    assert _read("short_conv_share", ctx) is None
    # joined once a run, and it says so once
    assert capsys.readouterr().err.count("scope_time:") == 1


def test_a_join_that_misses_two_percent_of_busy_time_reads_none(table):
    assert _read("mamba_proj_share", _ctx(stray_ms=0.9)) == pytest.approx(30)
    ctx = _ctx(stray_ms=2.0)
    assert scope_time.seconds_by_name(ctx) is None
    for metric in READERS:
        assert _read(metric, ctx) is None, metric


def test_no_table_or_no_window_reads_none(monkeypatch, table):
    short = _ctx()
    short["trace"]["/device:TPU:0"]["XLA Modules"] = short["trace"][
        "/device:TPU:0"]["XLA Modules"][:3]
    assert _read("unscoped_share", short) is None
    assert _read("unscoped_share", {"trace": {}}) is None
    monkeypatch.setattr(analysis, "step_program_scopes", lambda: None)
    assert _read("unscoped_share", _ctx()) is None
    # a program from before the table has no such function: nothing, no raise
    monkeypatch.delattr(analysis, "step_program_scopes")
    assert _read("unscoped_share", _ctx()) is None


@pytest.mark.parametrize("metric", sorted({**READERS, **LATER}))
def test_each_reader_resolves_and_is_on_its_cells(metric, monkeypatch):
    summed, cells, layer = {**READERS, **LATER}[metric]
    bench = loader.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": layer,
        "moves": "train_examples_per_s",
        **({} if cells == EVERY else {"workloads": list(cells)})}
    for w in bench["workloads"]:
        listed = metric in {m["name"] for m in loader.resolve_cell(
            w["name"], bench)["per_layer"]}
        assert listed == (w["name"] in cells), w["name"]
    mod = loader.load_module("layer_metrics", metric)
    assert callable(mod.read) and mod.__doc__
    # the reader sums the scopes its row names and no others
    seen = []
    monkeypatch.setattr(scope_time, "share",
                        lambda ctx, names: seen.append(tuple(names)))
    mod.read({})
    assert seen == [summed]


def test_the_entries_are_appended_and_nothing_else_changed():
    names = [m["name"] for m in loader.load_benchmark()["per_layer"]]
    assert names[20:27] == list(READERS)
    # later cells appended seven behind them, four of them by-scope readers
    assert names[27:] == ["gated_delta_fwd_roofline",
                          "gated_delta_bwd_roofline", "gated_delta_share",
                          *LATER]
    assert len(names) == len(set(names)) == 34
