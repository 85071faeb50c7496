"""The benchmark's FLOP and byte functions against hand counts."""
import json
import os

import pytest

from benchmarks.chip import cost

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(HERE, "..", "..", "benchmarks", "chip", "configs")


def _conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params,attn", [
    # Yi-6B, 4 layers: q,o 4096x4096, k,v 4096x512, up/gate/down 4096x11008,
    # head 64000x4096; attention 4 layers x 12 x 32 heads x 128 x 4097/2
    ("yi-6b-4l", 4 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
     + 64000 * 4096, 4 * 12 * 32 * 128 * 4097 / 2),
    # OLMoE, 2 layers: q,k,v,o 2048x2048, 8 of 64 experts of 3 x 2048x1024,
    # router 2048x64, head 50304x2048
    ("olmoe-1b-7b-2l", 2 * (4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 64 * 2048)
     + 50304 * 2048, 2 * 12 * 16 * 128 * 4097 / 2),
])
def test_model_flops_per_token_hand_count(name, params, attn):
    c = _conf(name)
    assert cost.matmul_params_per_token(c) == params
    assert cost.attention_flops_per_token(c, 4096) == pytest.approx(attn)
    assert cost.model_flops_per_token(c, 4096) == pytest.approx(6 * params + attn)


@pytest.mark.parametrize("name,flops", [("yi-6b-4l", 6127976448.0),
                                        ("olmoe-1b-7b-2l", 1525702656.0)])
def test_model_flops_per_token_is_the_llama_count(name, flops):
    # mfu's constant for the cells of these configurations, to the last bit
    c = _conf(name)
    assert cost.model_flops_per_token(c, 4096) == flops
    assert flops == 6 * cost.matmul_params_per_token(c) + cost.attention_flops_per_token(c, 4096)


def test_yi_flops_per_token_is_6_13_gflop():
    assert cost.model_flops_per_token(_conf("yi-6b-4l"), 4096) == pytest.approx(6.13e9, rel=1e-3)


# the HLO text a TPU trace gives a fused-kernel call at Yi's k projection:
# G [4096, 512] -> d 4096, one kept block of 128
FUSED = ("%block_gather_matmul_fused.24 = (bf16[4096,4096]{1,0:T(8,128)(2,1)}, "
         "bf16[1,128,4096]{2,1,0:T(8,128)(2,1)}, f32[1,128]{1,0:T(1,128)}) "
         "custom-call(s32[1]{0:T(128)} %bitcast.1136, f32[1]{0:T(128)S(6)} %div.810, "
         "bf16[4096,512]{1,0:T(8,128)(2,1)GSPACE} %copy.263, bf16[512,4096]{1,0:T(8,128)(2,1)} "
         "%w, bf16[4096,4096]{1,0:T(8,128)(2,1)} %x), custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("g_space,g_bytes", [("", 2 * 4096 * 128), ("S(1)", 0)])
def test_fused_kernel_work_at_one_site(g_space, g_bytes):
    flops, bytes_ = cost.kernel_work("block_gather_matmul_fused.24", FUSED.replace("GSPACE", g_space))
    # dX = Ĝ W and dW = Ĝ^T X over 128 kept columns
    assert flops == 2 * 2 * 4096 * 128 * 4096
    # kept G columns (unless G sits in VMEM), kept W rows, all of X; dX,
    # dW rows (bf16) and db (f32) written
    assert bytes_ == (g_bytes + 2 * 128 * 4096 + 2 * 4096 * 4096
                      + 2 * 4096 * 4096 + 2 * 128 * 4096 + 4 * 128)


def test_score_kernel_work():
    hlo = ("%col_l1_scores.84 = f32[1,4096]{1,0:T(1,128)S(1)} custom-call("
           "bf16[4096,4096]{1,0:T(8,128)(2,1)} %bitcast.1086), custom_call_target=\"x\"")
    assert cost.kernel_work("col_l1_scores.84", hlo) == (2 * 4096 * 4096, 2 * 4096 * 4096)
    assert cost.kernel_work("flash_attention.1", hlo) is None
