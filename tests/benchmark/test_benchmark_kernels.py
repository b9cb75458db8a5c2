"""Operations and bytes against hand-worked shapes."""
import pytest

from benchmark import harness, weights as W
from benchmark.kernels import flash, model, rpa

TRAIN = harness.load_json(harness.HERE, "configs",
                          "mistral-7b-v0.3-train-l2.json")
SERVE = harness.load_json(harness.HERE, "configs",
                          "mistral-7b-v0.3-serve-l16.json")
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"]["TPU v5 lite"]


def test_mistral_layer_is_218_1_million_parameters():
    n = W.n_params(TRAIN)
    # q and o 4096x4096, k and v 4096x1024; three 4096x14336; two gains
    assert n["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 == 41_943_040
    assert n["mlp"] == 3 * 4096 * 14336 == 176_160_768
    assert n["layer"] == 218_112_000
    assert n["embed"] == n["head"] == 32768 * 4096
    assert n["total"] == 2 * 218_112_000 + 2 * 134_217_728 + 4096
    assert W.n_params(SERVE)["total"] == 3_758_231_552


def test_train_flops_per_token():
    # 2 layers x 2 x 218.1 M + head 2 x 134.2 M, attention 4*32*128 a key at
    # the mean context 2048.5, all times 3 for forward + backward
    fwd = 2 * (2 * 218_103_808 + 4 * 32 * 128 * 2048.5) + 2 * 134_217_728
    assert model.train_flops_per_token(TRAIN, 4096) == pytest.approx(3 * fwd)
    assert 3.5e9 < 3 * fwd < 3.7e9


def test_rpa_bytes_and_flops_for_a_page_list():
    # one decode row over 5 pages of 128 (context 639 + 1 new) and one
    # prefill chunk of 112 on 256 cached: K and V read once, q in, o out
    rows = [(1, 639), (112, 256)]
    flops, nbytes = rpa.required(rows, heads=32, kv_heads=8, hd=128)
    kv = 2 * 8 * 128 * 2 * (640 + 368)
    qo = 2 * 32 * 128 * 2 * (1 + 112)
    assert nbytes == kv + qo
    seen = 1 * 640 + (112 * 256 + 112 * 113 / 2)
    assert flops == 4 * 32 * 128 * seen


def test_flash_required_and_roofline():
    need = flash.required(batch=2, heads=32, kv_heads=8, seq=4096, hd=128)
    unit = 2 * 2 * 32 * 4096 * 4096 * 128 * 0.5
    assert need["fwd"][0] == 2 * unit and need["bwd"][0] == 5 * unit
    seconds, bound = flash.least_seconds(*need["fwd"], PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(2 * unit / 197e12)
    assert flash.least_seconds(1e6, 1e9, PEAKS) == (1e9 / 819e9, "memory")
