"""Kimi-K2: the DeepSeek-V3 architecture under its own ``model_type``."""
from arch.deepseek_v3 import arch, logits, model_config, step_flops  # noqa: F401
