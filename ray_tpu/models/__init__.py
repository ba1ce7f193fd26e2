from ray_tpu.models import (afmoe, bert, deepseek, diffusion, dots3_note,
                            evabyte, exchange, glm_moe_dsa, gpt, granite,
                            kimi_linear, lfm2, lm, mellum, minicpm_sala,
                            nemotron_h, t5, vit)

__all__ = ["afmoe", "bert", "deepseek", "diffusion", "dots3_note", "evabyte",
           "exchange", "glm_moe_dsa", "gpt", "granite", "kimi_linear", "lfm2",
           "lm", "mellum", "minicpm_sala", "nemotron_h", "t5", "vit"]
