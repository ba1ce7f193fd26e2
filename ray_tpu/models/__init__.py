from ray_tpu.models import (bert, deepseek, diffusion, gpt, granite, lm, t5,
                            vit)

__all__ = ["bert", "deepseek", "diffusion", "gpt", "granite", "lm", "t5",
           "vit"]
