"""Model families shipped with the framework (TPU-native flax modules).

The reference ships no model implementations (its release gates pull
GPT-J/vicuna through external torch engines); here the flagship decoder,
an expert-parallel MoE, and the generation path are part of the framework.
`serve.LLMEngine` serves six of them (`serve/llm_families.py`):
`LlamaConfig`, `SambaYConfig`, `GraniteHybridConfig` (its dense members,
and with `n_experts` its routed ones: a shared feed-forward beside
`Lfm2MoeConfig`'s routed layer under Granite's own router, a chip holding
all of a layer's experts or a stated share of them), `Lfm2MoeConfig`
(routed experts with no token dropped and a cached decode path; `moe.py`'s
capacity-bounded layer trains at toy sizes and is not served),
`MlaMoeConfig` (latent attention over a latent paged cache, the same
routed layer with shared experts beside it) and `MiniCpmSalaConfig`
(block-sparse attention that chooses its pages through a pool of
compressed keys, among lightning linear-attention layers whose state
`granite_hybrid`'s scan advances).
"""

from ray_tpu.models.lfm2_moe import (
    LFM2_24B_A2B,
    TINY_LFM2_MOE,
    Lfm2MoeConfig,
    Lfm2MoeModel,
)
from ray_tpu.models.llama import (
    LLAMA2_7B,
    LLAMA2_13B,
    LLAMA3_8B,
    TINY,
    LlamaConfig,
    LlamaModel,
    cross_entropy_loss,
    init_kv_caches,
)
from ray_tpu.models.minicpm_sala import (
    MINICPM_SALA_L8,
    TINY_SALA,
    MiniCpmSalaConfig,
    MiniCpmSalaModel,
)
from ray_tpu.models.mla_moe import (
    KIMI_VL_A3B,
    TINY_MLA_MOE,
    MlaMoeConfig,
    MlaMoeModel,
)
from ray_tpu.models.moe import (
    MIXTRAL_8X7B,
    MOE_RULES,
    TINY_MOE,
    MoEConfig,
    MoEModel,
    moe_aux_loss,
)
from ray_tpu.models.dit import (
    DiT,
    DiTConfig,
    ddim_sample,
    ddpm_loss,
)
from ray_tpu.models.encoder import (
    BERT_BASE,
    BERT_LARGE,
    T5_BASE,
    T5_LARGE,
    TINY_ENCDEC,
    TINY_ENCODER,
    EncDecConfig,
    Encoder,
    EncoderConfig,
    EncoderDecoder,
    mlm_loss,
    seq2seq_loss,
)
from ray_tpu.models.generate import Generator, SamplingParams, generate
from ray_tpu.models.granite_hybrid import (
    GRANITE_4_H_MICRO,
    TINY_GRANITE,
    TINY_GRANITE_MOE,
    GraniteHybridConfig,
    GraniteHybridModel,
)
from ray_tpu.models.sambay import (
    PHI4_MINI_FLASH,
    TINY_SAMBAY,
    SambaYConfig,
    SambaYModel,
)
from ray_tpu.models.ssm import (
    MAMBA_130M,
    MAMBA_790M,
    TINY_SSM,
    SSM_RULES,
    SSMConfig,
    SSMModel,
    chunked_selective_scan,
    init_ssm_state,
    ssm_decode_step,
    ssm_prefill,
)
from ray_tpu.models.vit import (
    VIT_B16,
    VIT_L16,
    VIT_TINY,
    ViT,
    ViTConfig,
    vit_loss,
)

__all__ = [
    "LlamaConfig", "LlamaModel", "LLAMA2_7B", "LLAMA2_13B", "LLAMA3_8B",
    "TINY", "cross_entropy_loss", "init_kv_caches",
    "MoEConfig", "MoEModel", "MIXTRAL_8X7B", "TINY_MOE", "MOE_RULES",
    "moe_aux_loss",
    "Generator", "SamplingParams", "generate",
    "ViT", "ViTConfig", "VIT_B16", "VIT_L16", "VIT_TINY", "vit_loss",
    "DiT", "DiTConfig", "ddpm_loss", "ddim_sample",
    "Encoder", "EncoderConfig", "BERT_BASE", "BERT_LARGE", "TINY_ENCODER",
    "mlm_loss", "EncoderDecoder", "EncDecConfig", "T5_BASE", "T5_LARGE",
    "TINY_ENCDEC", "seq2seq_loss",
    "SSMModel", "SSMConfig", "MAMBA_130M", "MAMBA_790M", "TINY_SSM",
    "SSM_RULES", "init_ssm_state", "ssm_decode_step", "ssm_prefill",
    "chunked_selective_scan",
    "SambaYModel", "SambaYConfig", "PHI4_MINI_FLASH", "TINY_SAMBAY",
    "GraniteHybridModel", "GraniteHybridConfig", "GRANITE_4_H_MICRO",
    "TINY_GRANITE",
    "TINY_GRANITE_MOE",
    "Lfm2MoeModel", "Lfm2MoeConfig", "LFM2_24B_A2B", "TINY_LFM2_MOE",
    "MlaMoeModel", "MlaMoeConfig", "KIMI_VL_A3B", "TINY_MLA_MOE",
    "MiniCpmSalaModel", "MiniCpmSalaConfig", "MINICPM_SALA_L8", "TINY_SALA",
]
