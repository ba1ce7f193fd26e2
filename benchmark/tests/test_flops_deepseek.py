"""``flops_deepseek.py`` against a count made by hand from the published
widths of Moonlight-16B-A3B, for the cell's 4 layers and for all 27, and
the kernels' executed work against small cases counted by hand."""

import flops_deepseek

MOONLIGHT = {
    "hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 11264, "moe_intermediate_size": 1408,
    "n_routed_experts": 64, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "vocab_size": 163840,
    "num_hidden_layers": 27}

# By hand, per layer:
#   Wq 2048 x 16 x 192 = 6,291,456; W_kv_a 2048 x 576 = 1,179,648;
#   W_kv_b 512 x 16 x 256 = 2,097,152; Wo 16 x 128 x 2048 = 4,194,304
ATTENTION = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304  # 13,762,560
DENSE = 3 * 2048 * 11264                                   # 69,206,016
#   router 2048 x 64; 6 routed + 2 shared experts of 3 x 2048 x 1408
EXPERT_LAYER = 131_072 + 8 * 8_650_752                     # 69,337,088
HEAD = 2048 * 163840                                       # 335,544,320


def test_attention_params():
    assert flops_deepseek.attention_params(MOONLIGHT) == ATTENTION == \
        13_762_560


def test_the_cell_s_four_layers():
    config = dict(MOONLIGHT, num_hidden_layers=4)
    params = 4 * ATTENTION + DENSE + 3 * EXPERT_LAYER + HEAD
    assert params == 667_811_840
    assert flops_deepseek.active_matmul_params(config) == params
    attention = 6 * 4 * 16 * (192 + 128) * 8192  # 1,006,632,960
    assert flops_deepseek.model_flops_per_token(config, 8192) == \
        6.0 * params + attention == 5_013_504_000.0
    # The head is two fifths of it, as the configuration's file says.
    assert abs(6 * HEAD / 5_013_504_000.0 - 0.40) < 0.005


def test_all_27_layers():
    params = 27 * ATTENTION + DENSE + 26 * EXPERT_LAYER + HEAD
    assert params == 2_579_103_744  # the "A3B" less the embedding
    assert flops_deepseek.active_matmul_params(MOONLIGHT) == params
    assert flops_deepseek.model_flops_per_token(MOONLIGHT, 8192) == \
        6.0 * params + 6 * 27 * 16 * 320 * 8192


def test_causal_tiles():
    # 4 x 4 equal tiles: the lower triangle with the diagonal, 10.
    assert flops_deepseek.causal_tiles(512, 128, 128) == 10
    # Q tiles of 256, KV tiles of 128: rows see 2 and 4 KV tiles.
    assert flops_deepseek.causal_tiles(512, 256, 128) == 6
    # Q tiles of 128, KV tiles of 256: 1, 1, 2, 2.
    assert flops_deepseek.causal_tiles(512, 128, 256) == 6
    assert flops_deepseek.causal_tiles(8192, 512, 512) == 136


def test_flash_calls():
    fwd = flops_deepseek.flash_call("flash_fwd", 32, 8192, 192, 128, 512, 512)
    assert fwd["flops"] == 32 * 136 * 2 * 512 * 512 * (192 + 128)
    assert fwd["bytes"] == 32 * 8192 * 2 * (2 * 192 + 2 * 128)
    dq = flops_deepseek.flash_call("flash_bwd_dq", 32, 8192, 192, 128, 512,
                                   512)
    assert dq["flops"] == 32 * 136 * 2 * 512 * 512 * (2 * 192 + 128)
    dkv = flops_deepseek.flash_call("flash_bwd_dkv", 32, 8192, 192, 128, 512,
                                    512)
    assert dkv["flops"] == 32 * 136 * 2 * 512 * 512 * (2 * 192 + 2 * 128)
    assert dkv["bytes"] == 32 * 8192 * 2 * (3 * 192 + 3 * 128)


def test_grouped_matmul_layer_and_the_step():
    layer = flops_deepseek.grouped_matmul_layer(MOONLIGHT, 16384, remat=True)
    assert layer["products"] == 12 and layer["tgmm"] == 3
    assert layer["flops"] == 12 * 2.0 * 98304 * 2048 * 1408
    assert layer["bytes"] == 12 * (98304 * (2048 + 1408) * 2
                                   + 64 * 2048 * 1408 * 2)
    assert flops_deepseek.grouped_matmul_layer(
        MOONLIGHT, 16384, remat=False)["products"] == 9
    config = dict(MOONLIGHT, num_hidden_layers=4)
    step = flops_deepseek.step_kernel_flops(config, 2, 8192, 512, 512, True)
    assert step["grouped_matmul"] == 3 * layer["flops"]
    tile = 32 * 136 * 2 * 512 * 512
    assert step["flash_fwd"] == 4 * 2 * tile * 320
    assert step["flash_bwd_dq"] == 4 * tile * 512
    assert step["flash_bwd_dkv"] == 4 * tile * 640
