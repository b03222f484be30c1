"""Flagship model zoo (TPU-native)."""
from paddle_tpu.models.deepseek_v3 import (  # noqa: F401
    DeepseekV3Config, DeepseekV3ForCausalLM,
)
from paddle_tpu.models.gpt import (  # noqa: F401
    GPT, GPTBlock, GPTConfig, build_pipeline_train_step, gpt_loss_fn,
)
from paddle_tpu.models.ernie import (  # noqa: F401
    ErnieConfig, ErnieForPretraining, ErnieForSequenceClassification,
    ErnieForTokenClassification, ErnieModel, ernie_pretrain_loss_fn,
    mask_tokens,
)
from paddle_tpu.models.laguna import (  # noqa: F401
    LagunaConfig, LagunaForCausalLM,
)
from paddle_tpu.models.olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig, OlmoHybridForCausalLM,
)
from paddle_tpu.models.phi4flash import (  # noqa: F401
    Phi4FlashConfig, Phi4FlashForCausalLM,
)
from paddle_tpu.models.llama import (  # noqa: F401
    Llama, LlamaConfig, llama_loss_fn,
)
from paddle_tpu.models.zaya import (  # noqa: F401
    ZayaConfig, ZayaForCausalLM, zaya_loss_fn,
)
