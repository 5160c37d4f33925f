"""The benchmark's operation counts against hand counts."""
import json
from pathlib import Path

import pytest

from bench import flops as F

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def qwen2(n_layers):
    return {"n_layers": n_layers, "d_model": 1536, "n_heads": 12,
            "n_kv_heads": 2, "d_ff": 8960, "vocab_size": 151936,
            "qkv_bias": True, "tie_embeddings": True}


@pytest.mark.parametrize("layers, params", [(28, 1_543_714_304),
                                            (8, 607_757_824)])
def test_param_count(layers, params):
    # per layer: two norms 2 * 1536; q and o 1536^2 each; k and v
    # 1536 x 256 each (2 heads of 128); q/k/v biases 1536 + 2 * 256; three
    # 1536 x 8960 MLP matrices; the tied embedding 151936 x 1536 once and
    # the final norm 1536
    assert (2 * 1536 + 2 * 1536 ** 2 + 2 * 1536 * 256 + 1536 + 512
            + 3 * 1536 * 8960) == 46_797_824
    assert F.param_count(qwen2(layers)) == params


def test_train_flops_are_six_n_plus_attention():
    m, seq = qwen2(8), 2048
    n_matmul = (8 * (2 * 1536 ** 2 + 2 * 1536 * 256 + 3 * 1536 * 8960)
                + 1536 * 151936)
    attn = 3 * 8 * 2 * 12 * 128 * seq          # fwd + bwd, causal half
    assert F.train_flops_per_token(m, seq) == 6 * n_matmul + attn
    # 6N where N leaves out the norms and biases, and counts the tied
    # embedding once, as the LM head
    n = F.param_count(m) - (2 * 8 + 1) * 1536 - 8 * (1536 + 512)
    assert F.train_flops_per_token(m, seq) == 6 * n + attn


def test_config_file_counts():
    from bench.reference import dense_ref
    cfg = json.loads((CONFIGS / "qwen2-1.5b-l8.json").read_text())
    m = dense_ref.arch(cfg)
    assert F.param_count(m) == 607_757_824
    assert F.param_count(dict(
        m, n_layers=cfg["published"]["num_hidden_layers"])) == 1_543_714_304


def test_group_reduce_cost_from_hlo_text():
    text = ("%group_min_scale.1 = f32[1,2048]{1,0:T(1,128)S(1)} custom-call("
            "f32[1]{0:T(128)} %bitcast.4, f32[64,2048]{1,0:T(8,128)S(1)} "
            "%pad.0), custom_call_target=\"tpu_custom_call\"")
    ops, nbytes = F.group_reduce_cost(text)
    assert nbytes == 4 * 1              # only the scalar is in HBM
    assert ops == 64 * 2048 + 3 * 2048
    text = ("%group_max.3 = f32[1,1024]{1,0} custom-call(f32[256,1024]{1,0} "
            "%pad.2), custom_call_target=\"tpu_custom_call\"")
    ops, nbytes = F.group_reduce_cost(text)
    assert (ops, nbytes) == (256 * 1024, 4 * (1024 + 256 * 1024))
