"""The FLOPs-per-item and bytes functions against counts made by hand,
the peaks table, and the refusal of a share over 100%."""

import os

import pytest

from perfbench.harness import loader, peaks

from conftest import REPO


def load(name):
    return loader.load_module(REPO, "references", name)


def mistral(cut):
    cfg = loader.read_json(os.path.join(
        REPO, "perfbench", "configs", "mistral-7b-v0.1.json"))
    fam = loader.load_module(REPO, "families", "dense_gqa_decoder")
    return fam.sizes(cfg, cut)


def test_mistral_parameter_counts_match_the_configuration_file():
    ref = load("dense_gqa_decoder")
    sz = mistral("train")
    # one layer: wq 4096x4096, wk and wv 4096x1024, wo 4096x4096,
    # three FFN matrices 4096x14336
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert ref.matmul_params(sz) == 2 * layer + 4096 * 32000
    assert ref.total_params(sz) == (2 * layer + 2 * 4096 * 32000
                                    + 5 * 4096)
    assert round(ref.total_params(sz) / 1e6, 1) == 698.4
    assert round(ref.total_params(mistral("serve")) / 1e9, 2) == 3.75


def test_mistral_train_flops_a_token_by_hand():
    ref = load("dense_gqa_decoder")
    sz = mistral("train")
    matmul = 2 * 567_279_616               # 2 FLOPs a parameter
    # causal attention at 4096: QK^T and PV, 32 heads of 128, a token
    # sees (4096 + 1) / 2 keys on average, two layers
    attention = 2 * (2 * 2 * 32 * 128 * 4097 / 2)
    want = 3 * (matmul + attention)
    got = ref.train_flops_per_item(sz, {"seq_len": 4096})
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(3.605e9, rel=1e-3)


def test_flash_kernel_cost_by_hand():
    ref = load("dense_gqa_decoder")
    flops, nbytes = ref.flash_kernel_cost(mistral("train"), 2, 4096)
    unit = 2 * 2 * 32 * 128 * (4096 * 4097 / 2)   # one product, causal
    assert flops == pytest.approx(7 * unit)
    q = 2 * 4096 * 32 * 128 * 2
    kv = 2 * 4096 * 8 * 128 * 2
    assert nbytes == 6 * q + 6 * kv
    least, bound = peaks.roofline_seconds(flops, nbytes,
                                          peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(flops / 197e12)


def test_decode_step_bytes_by_hand():
    ref = load("dense_gqa_decoder")
    sz = mistral("serve")
    weights = (16 * 218_103_808 + 4096 * 32000) * 2
    per_token = 16 * 2 * 8 * 128 * 2
    assert per_token == 65536                      # 64 KiB a token
    assert ref.decode_step_bytes(sz, 0) == weights
    assert ref.decode_step_bytes(sz, 1000) == weights + 1000 * per_token
    assert weights == pytest.approx(7.24e9, rel=1e-2)


def test_resnet50_flops_an_image_by_hand():
    ref = load("resnet")
    cfg = loader.read_json(os.path.join(REPO, "perfbench", "configs",
                                        "resnet50.json"))
    fam = loader.load_module(REPO, "families", "resnet")
    sz = fam.sizes(cfg)
    # the well-known figure: 4.09 G multiply-adds forward at 224 x 224
    # (torchvision's count for resnet50, stride on the 3x3)
    macs = ref.forward_flops_per_image(sz) / 2
    assert macs == pytest.approx(4.09e9, rel=0.01)
    # the stem alone: 7*7*3*64 multiply-adds at 112 x 112
    assert ref.forward_flops_per_image(
        dict(sz, stage_sizes=[])) == 2 * (7 * 7 * 3 * 64 * 112 * 112
                                         + 64 * 1000)
    assert ref.train_flops_per_item(sz, {}) == 3 * 2 * macs
    n = sum(int(__import__("numpy").prod(shape))
            for coll, _, shape, _ in fam._leaves(sz) if coll == "params")
    assert n == 25_557_032                         # torchvision's count


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9")


def test_a_share_over_100_percent_raises_instead_of_printing():
    assert peaks.share_pct(0.5, 1.0, "x") == 50.0
    assert peaks.share_pct(1.0, 1.0, "x") == 100.0
    with pytest.raises(ValueError, match="cannot be right"):
        peaks.share_pct(1.01, 1.0, "x")
    with pytest.raises(ValueError):
        peaks.share_pct(1.0, 0.0, "x")
    mfu = loader.load_module(REPO, "layer_metrics", "train_mfu_pct")
    ctx = {"peaks": peaks.peaks_for("TPU v5 lite"), "flops_per_item": 3.6e9,
           "rate_per_chip": 28_000.0}
    assert mfu.reduce(None, None, ctx) == pytest.approx(51.17, rel=1e-3)
    with pytest.raises(ValueError):
        mfu.reduce(None, None, dict(ctx, rate_per_chip=60_000.0))
