"""Each formula on a shape small enough to work by hand."""
import opcount

CFG = {"hidden_size": 4, "ffn_hidden": 16, "num_layers": 2, "vocab_size": 10,
       "num_heads": 2}
GPT2 = {"hidden_size": 768, "ffn_hidden": 3072, "num_layers": 12,
        "vocab_size": 50304, "num_heads": 12}


def test_matmul_params():
    # per layer 4*4*4 (qkv 48 + out 16) + 2*4*16 = 192; x2 layers; head 40
    assert opcount.matmul_params(CFG) == 2 * 192 + 40


def test_attention_flops():
    # one sequence of 8: QK^T and PV, causal half: s*s*h each = 256 each;
    # two matmuls, two layers -> 1024
    assert opcount.attention_flops_fwd(CFG, 8) == 1024
    # forward 2 matmuls + backward 4 = 3x forward, batch 3
    assert opcount.attention_flops_train(CFG, 3, 8) == 3 * 3 * 1024


def test_train_flops_per_token():
    # 3 x (2 x 424 params + 1024/8 attention per token)
    assert opcount.train_flops_per_token(CFG, 8) == 3 * (2 * 424 + 128)
    # GPT-2 124M at 1024: the 8.0e8 of PERF.md
    assert abs(opcount.train_flops_per_token(GPT2, 1024) / 7.98e8 - 1) < 0.01


def test_decode_bytes():
    # K and V, 2 layers, hidden 4, 2 bytes: 32 bytes a context token
    assert opcount.kv_bytes_per_token(CFG, 2) == 32
    assert opcount.decode_attention_bytes(CFG, [3, 5], 2) == 8 * 32
    gpt3 = {"hidden_size": 2048, "num_layers": 24}
    assert opcount.kv_bytes_per_token(gpt3, 2) == 196608
