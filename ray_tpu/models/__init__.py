from .llama import LlamaConfig, LlamaForCausalLM, CONFIGS  # noqa: F401
from .mixtral import MixtralConfig, MixtralForCausalLM, moe_lm_loss  # noqa: F401
from .mixtral import CONFIGS as MIXTRAL_CONFIGS  # noqa: F401
