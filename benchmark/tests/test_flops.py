"""``flops.py`` against a count made by hand for the whole GPT-J-6B."""

import pytest

import flops
import harness


def test_gptj_6b_by_hand():
    # Per layer: Wq, Wk, Wv, Wo are 4096 x 4096 each; the FFN is
    # 4096 x 16384 and back. The head is 4096 x 50400. The input embedding
    # (50400 x 4096 = 206,438,400) is a lookup and is not counted.
    per_layer = 4 * 4096 * 4096 + 2 * 4096 * 16384
    assert per_layer == 201_326_592
    matmul = 28 * per_layer + 4096 * 50400
    assert matmul == 5_843_582_976
    assert flops.matmul_params(28, 4096, 16384, 50400, 16, 256) == matmul
    attention = 12 * 28 * 4096 * 2048
    assert attention == 2_818_572_288
    assert flops.train_flops_per_token(
        28, 4096, 16384, 50400, 16, 256, 2048) == 6 * matmul + attention
    assert 6 * matmul + attention == 37_880_070_144


def test_from_the_configuration_files():
    spec = harness.load_spec()
    by_layers = {}
    for entry in spec["configs"]:
        config = harness.load_json(
            harness.os.path.join(harness.ROOT, entry["file"]))
        if "n_layer" not in config:
            # Another family's published keys: its count is a file of its
            # own (``flops_<family>.py``) with tests of its own.
            continue
        by_layers[config["n_layer"]] = flops.model_flops_per_token(
            config, config["layout"]["seq_len"])
    assert by_layers[28] == 37_880_070_144
    # The embedding is not in N: the program's own figure is larger.
    from ray_tpu.models import gpt
    assert gpt.flops_per_token(gpt.config("gptj-6b")) - by_layers[28] \
        > 6 * 206_438_400 - 1e7


def test_unknown_device_is_an_error():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no published"):
        flops.peak("cpu")
