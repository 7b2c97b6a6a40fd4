"""flops.py against hand-worked values."""

import json
import os

import pytest

from perfbench import flops, manifest


def _config():
    path = os.path.join(manifest.HERE, "configs", "starcoderbase_7b_train.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("op,factor", [
    ("allreduce", 1.5), ("allgather", 0.75),
    ("reduce_scatter", 0.75), ("alltoall", 0.75),
])
def test_bus_bytes_at_world_4(op, factor):
    # nccl-tests: allreduce 2(P-1)/P = 6/4, the others (P-1)/P = 3/4
    assert flops.bus_bytes(op, 64 << 20, 4) == factor * (64 << 20)


def test_bus_bytes_refuses_an_unknown_op():
    with pytest.raises(KeyError):
        flops.bus_bytes("bcast", 1024, 4)


def test_matmul_params_by_hand():
    cfg = _config()
    d, ff, hd, v = 4096, 16384, 128, 49152
    layer = d * d + 2 * d * hd + d * d + 2 * d * ff   # q, k+v (MQA), o, ffn
    assert layer == 168_820_736
    assert flops.matmul_params(cfg) == 6 * layer + d * v == 1_214_251_008


@pytest.mark.parametrize("seq,gflop", [(1024, 7.4365), (8192, 8.4935)])
def test_train_flops_per_token_by_hand(seq, gflop):
    cfg = _config()
    # 6 x 1,214,251,008 = 7.2855 GFLOP of matmuls; attention adds
    # 6 * T * d a layer a token: 0.1510 at T=1024, 1.2080 at T=8192
    matmul = 6 * 1_214_251_008
    attn = 6 * (6 * seq * 4096)
    assert flops.train_flops_per_token(cfg, seq) == matmul + attn
    assert flops.train_flops_per_token(cfg, seq) / 1e9 == pytest.approx(
        gflop, abs=1e-3
    )


def test_attention_is_compute_bound_at_both_lengths():
    cfg = _config()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for seq in (1024, 8192):
        f = flops.attention_train_flops(cfg, seq)
        assert f == 6 * seq * seq * 4096
        least, bound = flops.roofline_seconds(
            f, flops.attention_train_bytes(cfg, seq), peaks
        )
        assert bound == "compute"
        assert least == f / 197e12


def test_roofline_names_the_memory_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(1e9, 819e9, peaks)
    assert bound == "memory" and least == 1.0
