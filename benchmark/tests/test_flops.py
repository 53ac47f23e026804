"""The FLOP functions against hand-worked numbers."""

import tiny  # noqa: F401  (puts benchmark/ on the path)
from harness import loader


def _cfg(name):
    return loader.load_json(loader.bench_path("configs", name + ".json"))


def test_resnet50_forward_is_4_1_gmac():
    f = loader.load_module("flops", "resnet50")
    macs = f.forward_macs(_cfg("resnet50"))
    # He et al. Table 1 gives 3.8e9 for v1; the v1.5 stride placement runs
    # the first 1x1 of each down-sampling block at the larger size: 4.09e9
    assert 4.05e9 < macs < 4.13e9, macs
    # the stem by hand: 112*112 outputs x 7*7*3 x 64
    assert 112 * 112 * 147 * 64 == 118013952
    assert f.train_flops_per_example(_cfg("resnet50"), {}) == 6 * macs


def test_resnet50_v1_placement_is_3_86_gmac():
    f = loader.load_module("flops", "resnet50")
    cfg = dict(_cfg("resnet50"), stride_on_3x3=False)
    assert 3.80e9 < f.forward_macs(cfg) < 3.90e9


def test_bert_base_matmul_parameters_are_84_9_million():
    f = loader.load_module("flops", "bert-base-uncased")
    cfg = _cfg("bert-base-uncased")
    # per layer 4 * 768^2 + 2 * 768 * 3072 = 7,077,888; twelve of them
    assert f.matmul_params(cfg) == 12 * 7077888 == 84934656


def test_bert_base_train_flops_per_token_at_384():
    f = loader.load_module("flops", "bert-base-uncased")
    cfg = _cfg("bert-base-uncased")
    traffic = loader.load_json(loader.bench_path("traffic",
                                                 "squad-s384-b32.json"))
    per_seq = f.train_flops_per_example(cfg, traffic)
    per_token = per_seq / 384
    # 6 * 84.9e6 + 12 layers * 12 * 384 * 768 = 509.6e6 + 42.5e6
    assert abs(per_token - (6 * 84934656 + 12 * 12 * 384 * 768)) < 1e5
