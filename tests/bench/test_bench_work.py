"""The work model and the share arithmetic, against values worked by hand
from the published starcoder2-3b and VGG-16 shapes."""
import json
import os

import pytest

from bench.work import lm, roofline, vgg

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
V5E = {"ops_per_s": {"bf16": 197e12, "int8": 393e12},
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def model(name):
    with open(os.path.join(REPO, "bench", "configs", name)) as f:
        return json.load(f)["model"]


def test_peaks_table_and_unknown_device():
    assert roofline.load_peaks("TPU v5 lite") == V5E
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9000")


def test_starcoder_sizes():
    m = model("starcoder2_3b-det.json")
    # per layer: 3072x3584 + 3072x3072 + 2 x 3072x12288 = 95,944,704
    assert lm.proj_params(m) == 30 * 95_944_704
    # per layer: (96*3584 + 96*3072 + 96*12288 + 384*3072) words * 4
    # + (3584 + 3072 + 12288 + 3072) scales * 4 = 12,081,152 bytes
    assert lm.packed_weight_bytes(m) == 30 * 12_081_152
    assert lm.head_bytes(m) == 3072 * 49152 * 2
    assert lm.kv_row_bytes(m) == 30 * 1024          # 30 KiB per token


def test_binary_matmul_call_by_hand():
    w = lm.binary_matmul_call(16, 3072, 12288)
    assert w["ops"] == {"bf16": 2.0 * 16 * 3072 * 12288}
    # x bf16 + packed words + f32 scale + f32 out
    assert w["bytes"] == 98_304 + 4_718_592 + 49_152 + 786_432
    s, bound = roofline.least_seconds(w, V5E)
    assert bound == "memory"
    assert s == pytest.approx(5_652_480 / 819e9)


def test_decode_step_by_hand():
    m = model("starcoder2_3b-det.json")
    w = lm.step(m, n=16, rows=16_000)
    ops = (2 * 30 * 95_944_704 * 16 + 30 * 4 * 24 * 128 * 16_000
           + 2 * 3072 * 49152 * 16)
    assert w["ops"]["bf16"] == pytest.approx(ops)
    nbytes = (30 * 12_081_152 + 3072 * 49152 * 2 + 30_720 * 16_000
              + 16 * (30_720 + 2 * 3072 * 2))
    assert w["bytes"] == pytest.approx(nbytes)
    # the weights are read once per call, whatever the call carries
    fused = lm.step(m, n=16, rows=16_000, chunk=(256, 256))
    assert fused["bytes"] - w["bytes"] == pytest.approx(
        30_720 * 512 + 256 * 3072 * 2)


def test_vgg_forward_by_hand():
    m = model("vgg16_cifar10-xnor.json")
    layers = list(vgg.layers(m, 1))
    assert [n for n, *_ in layers][:3] == ["conv/0", "conv/1", "conv/2"]
    name, kind, mm, k, n, i, o, wb = layers[2]
    assert (kind, mm, k, n) == ("xnor_conv", 16 * 16, 9 * 64, 128)
    assert wb == 9 * 2 * 128 * 4 + 128 * 4       # per-tap words + scale
    w = vgg.forward(m, 1)
    conv_int8 = sum(2 * mm * k * n for _, kind, mm, k, n, *_ in layers
                    if kind in ("xnor", "xnor_conv"))
    assert w["ops"]["int8"] == conv_int8
    # conv/0 and conv/1 on the bf16 peak: 2*1024*27*64 + 2*1024*576*64
    assert w["ops"]["bf16"] == pytest.approx(
        2 * 1024 * 27 * 64 + 2 * 1024 * 576 * 64
        + 2 * 512 * 512 + 2 * 512 * 10)
    assert vgg.forward(m, 256)["bytes"] == pytest.approx(256 * (
        w["bytes"] - sum(wb for *_, wb in layers)) + sum(
        wb for *_, wb in layers))


def test_shares():
    w = roofline.work({"bf16": 197e12, "int8": 393e12}, 0)
    assert roofline.least_seconds(w, V5E) == (2.0, "compute")
    w = roofline.work({"bf16": 1.0}, 819e9)
    assert roofline.least_seconds(w, V5E) == (1.0, "memory")
    # at its least time a call reads 100%; the share is never reported for
    # a reading of nothing
    assert roofline.share_percent(1.0, 1.0) == 100.0
    assert roofline.share_percent(1.0, 4.0) == 25.0
    assert roofline.share_percent(1.0, 0.0) is None
    assert roofline.share_percent(0.0, 1.0) is None
