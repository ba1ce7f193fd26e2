from ray_tpu.models import bert, deepseek, diffusion, gpt, lm, t5, vit

__all__ = ["bert", "deepseek", "diffusion", "gpt", "lm", "t5", "vit"]
