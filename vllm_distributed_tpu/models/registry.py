"""Architecture registry (reference: vllm/model_executor/models/registry.py:32
maps HF ``architectures`` strings to model classes; ~180 entries there).

The Llama-family functional model covers every config that is structurally
a pre-norm RoPE decoder with SwiGLU MLP and optional QKV bias — which is
Llama 2/3, Mistral, Qwen2, and friends.
"""

from vllm_distributed_tpu.models.families import (BaichuanForCausalLM,
                                                  Gemma2ForCausalLM,
                                                  Gemma3ForCausalLM,
                                                  GemmaForCausalLM,
                                                  InternLM2ForCausalLM,
                                                  Phi3ForCausalLM,
                                                  Qwen3ForCausalLM)
from vllm_distributed_tpu.models.deepseek import (DeepseekV2ForCausalLM,
                                                  DeepseekV3ForCausalLM)
from vllm_distributed_tpu.models.llama import (LlamaArchConfig,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.models.families_ext import (Cohere2ForCausalLM,
                                                      FlexOlmoForCausalLM,
                                                      GraniteMoeSharedForCausalLM,
                                                      HunYuanDenseV1ForCausalLM,
                                                      VaultGemmaForCausalLM,
                                                      CohereForCausalLM,
                                                      DbrxForCausalLM,
                                                      Exaone4ForCausalLM,
                                                      SmolLM3ForCausalLM,
                                                      FalconForCausalLM,
                                                      Glm4ForCausalLM,
                                                      GlmForCausalLM,
                                                      GptOssForCausalLM,
                                                      GraniteMoeForCausalLM,
                                                      OlmoeForCausalLM,
                                                      OlmoForCausalLM,
                                                      GPTNeoXForCausalLM,
                                                      GraniteForCausalLM,
                                                      NemotronForCausalLM,
                                                      Olmo2ForCausalLM,
                                                      Olmo3ForCausalLM,
                                                      PersimmonForCausalLM,
                                                      PhiForCausalLM,
                                                      PhimoeForCausalLM,
                                                      Qwen3MoeForCausalLM,
                                                      StableLmForCausalLM,
                                                      Starcoder2ForCausalLM)
from vllm_distributed_tpu.models.families_gpt import (ArceeForCausalLM,
                                                      BioGptForCausalLM,
                                                      XGLMForCausalLM,
                                                      BloomForCausalLM,
                                                      Ernie45ForCausalLM,
                                                      ExaoneForCausalLM,
                                                      SeedOssForCausalLM,
                                                      GPT2LMHeadModel,
                                                      GPTBigCodeForCausalLM,
                                                      GPTJForCausalLM,
                                                      MiniCPMForCausalLM,
                                                      MPTForCausalLM,
                                                      OPTForCausalLM)
from vllm_distributed_tpu.models.bert import (BertEmbeddingModel,
                                              BertForSequenceClassification,
                                              RobertaEmbeddingModel,
                                              RobertaForSequenceClassification)
from vllm_distributed_tpu.models.llava import LlavaForConditionalGeneration
from vllm_distributed_tpu.models.qwen2_vl import \
    Qwen2VLForConditionalGeneration
from vllm_distributed_tpu.models.bart import BartForConditionalGeneration
from vllm_distributed_tpu.models.whisper import \
    WhisperForConditionalGeneration
from vllm_distributed_tpu.models.bailing import BailingMoeV3ForCausalLM
from vllm_distributed_tpu.models.brumby import BrumbyForCausalLM
from vllm_distributed_tpu.models.bamba import BambaForCausalLM
from vllm_distributed_tpu.models.cohere_moe import Cohere2MoeForCausalLM
from vllm_distributed_tpu.models.granite_hybrid import \
    GraniteMoeHybridForCausalLM
from vllm_distributed_tpu.models.jamba import JambaForCausalLM
from vllm_distributed_tpu.models.smallthinker import SmallThinkerForCausalLM
from vllm_distributed_tpu.models.mamba import (FalconMambaForCausalLM,
                                               Mamba2ForCausalLM,
                                               MambaForCausalLM)
from vllm_distributed_tpu.models.moe_mixed import (Dots1ForCausalLM,
                                                   Ernie45MoeForCausalLM,
                                                   Glm4MoeForCausalLM)
from vllm_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                 Qwen2MoeForCausalLM)
from vllm_distributed_tpu.models.zaya import ZayaForCausalLM

_REGISTRY: dict[str, type] = {
    "LlamaForCausalLM": LlamaForCausalLM,
    "MistralForCausalLM": LlamaForCausalLM,
    # Ministral: llama block + uniform sliding window via layer_types
    # (the generic window resolver covers it).
    "MinistralForCausalLM": LlamaForCausalLM,
    "Qwen2ForCausalLM": LlamaForCausalLM,
    # Llama-weight-compatible forks (identical tensor naming + math).
    "AquilaForCausalLM": LlamaForCausalLM,
    "YiForCausalLM": LlamaForCausalLM,
    "MixtralForCausalLM": MixtralForCausalLM,
    "Qwen2MoeForCausalLM": Qwen2MoeForCausalLM,
    "GemmaForCausalLM": GemmaForCausalLM,
    "Gemma2ForCausalLM": Gemma2ForCausalLM,
    "Gemma3ForCausalLM": Gemma3ForCausalLM,
    "Qwen3ForCausalLM": Qwen3ForCausalLM,
    "Phi3ForCausalLM": Phi3ForCausalLM,
    "InternLM2ForCausalLM": InternLM2ForCausalLM,
    # Both checkpoint spellings; 13B (ALiBi) is rejected at load.
    "BaichuanForCausalLM": BaichuanForCausalLM,
    "BaiChuanForCausalLM": BaichuanForCausalLM,
    # MLA + DeepSeekMoE family (latent KV cache; models/deepseek.py).
    "DeepseekV2ForCausalLM": DeepseekV2ForCausalLM,
    "DeepseekV3ForCausalLM": DeepseekV3ForCausalLM,
    # Image+text (pre-computed projector embeddings; models/llava.py).
    "LlavaForConditionalGeneration": LlavaForConditionalGeneration,
    # Qwen2-VL family: M-RoPE decoder + dynamic-resolution tower with
    # video inputs (models/qwen2_vl.py).
    "Qwen2VLForConditionalGeneration": Qwen2VLForConditionalGeneration,
    # Families on the generic block knobs (models/families_ext.py).
    "GraniteForCausalLM": GraniteForCausalLM,
    "GraniteMoeForCausalLM": GraniteMoeForCausalLM,
    # GraniteMoe + ungated dense shared MLP (families_ext.py).
    "GraniteMoeSharedForCausalLM": GraniteMoeSharedForCausalLM,
    # Tencent HunYuan dense: llama + per-head qk RMSNorm.
    "HunYuanDenseV1ForCausalLM": HunYuanDenseV1ForCausalLM,
    # FlexOlmo: OLMo-2 post-norm block + OLMoE routed experts.
    "FlexOlmoForCausalLM": FlexOlmoForCausalLM,
    # ERNIE-4.5 MoE: dense prefix + bias-selected softmax routing +
    # ungated shared experts (models/moe_mixed.py).
    "Ernie4_5_MoeForCausalLM": Ernie45MoeForCausalLM,
    # GLM-4-MoE: dense prefix + DeepSeek-V3-style sigmoid routing +
    # shared experts on a standard-attention block (moe_mixed.py).
    "Glm4MoeForCausalLM": Glm4MoeForCausalLM,
    # dots.llm1: the GLM-4-MoE recipe + always-on per-head qk norm.
    "Dots1ForCausalLM": Dots1ForCausalLM,
    "DbrxForCausalLM": DbrxForCausalLM,
    # Attention sinks + clamped-GLU MoE (models/families_ext.py).
    "GptOssForCausalLM": GptOssForCausalLM,
    # Sparsemixer routing (models/families_ext.py PhimoeForCausalLM).
    "PhimoeForCausalLM": PhimoeForCausalLM,
    "Qwen3MoeForCausalLM": Qwen3MoeForCausalLM,
    "Starcoder2ForCausalLM": Starcoder2ForCausalLM,
    "StableLmForCausalLM": StableLmForCausalLM,
    "GPTNeoXForCausalLM": GPTNeoXForCausalLM,
    "PhiForCausalLM": PhiForCausalLM,
    "CohereForCausalLM": CohereForCausalLM,
    # Cohere2 / Command-R7B: sliding/full interleave, full layers NoPE.
    "Cohere2ForCausalLM": Cohere2ForCausalLM,
    # SmolLM3: llama block, every fourth layer NoPE.
    "SmolLM3ForCausalLM": SmolLM3ForCausalLM,
    # EXAONE-4: post-norm + per-head qk norm + hybrid global-NoPE.
    "Exaone4ForCausalLM": Exaone4ForCausalLM,
    # VaultGemma: Gemma block + softcaps/query scaling, no sandwich
    # norms (families_ext.py).
    "VaultGemmaForCausalLM": VaultGemmaForCausalLM,
    "Olmo2ForCausalLM": Olmo2ForCausalLM,
    "NemotronForCausalLM": NemotronForCausalLM,
    "OlmoForCausalLM": OlmoForCausalLM,
    "OlmoeForCausalLM": OlmoeForCausalLM,
    "GlmForCausalLM": GlmForCausalLM,
    "Glm4ForCausalLM": Glm4ForCausalLM,
    # OLMo-3: OLMo-2 post-norm block + windows + rope scaling only on
    # full-attention layers (models/families_ext.py).
    "Olmo3ForCausalLM": Olmo3ForCausalLM,
    "FalconForCausalLM": FalconForCausalLM,
    "PersimmonForCausalLM": PersimmonForCausalLM,
    # Selective state-space family (segmented-scan SSM; models/mamba.py).
    "MambaForCausalLM": MambaForCausalLM,
    "Mamba2ForCausalLM": Mamba2ForCausalLM,
    "FalconMambaForCausalLM": FalconMambaForCausalLM,
    # Hybrid attention/mamba/MoE (hybrid cache groups; models/jamba.py).
    "JambaForCausalLM": JambaForCausalLM,
    # Hybrid Mamba-2/attention (models/bamba.py).
    "BambaForCausalLM": BambaForCausalLM,
    # Granite 4.0-H: Bamba's stack with Granite's multipliers
    # (models/granite_hybrid.py).
    "GraniteMoeHybridForCausalLM": GraniteMoeHybridForCausalLM,
    # ZAYA1: compressed convolutional attention on carried rows, a
    # top-1 MLP router with a state from layer to layer (models/zaya.py).
    "ZayaForCausalLM": ZayaForCausalLM,
    # Command A+: Cohere's parallel block over sigmoid-routed experts
    # and averaged shared ones, window and full layers in cache groups
    # of their own (models/cohere_moe.py; the name is the family's
    # convention, the source gives ``model_type`` only).
    "Cohere2MoeForCausalLM": Cohere2MoeForCausalLM,
    # SmallThinker: the router reads the attention's input, ReLU-gated
    # experts, full layers without rope among window layers, in cache
    # groups of their own (models/smallthinker.py; the name is the
    # family's convention, the source gives ``model_name`` only).
    "SmallThinkerForCausalLM": SmallThinkerForCausalLM,
    # Ling-3.0: gated delta-rule linear attention (KDA) beside latent
    # attention over DeepSeek-V3 experts (models/bailing.py; the name is
    # the family's convention, not the source's).
    "BailingMoeV3ForCausalLM": BailingMoeV3ForCausalLM,
    # Brumby: Qwen3's block with power retention in every layer, a
    # float32 state a layer and no K/V page (models/brumby.py; the name
    # is the family's convention, the source gives ``model_type`` only).
    "BrumbyForCausalLM": BrumbyForCausalLM,
    # GPT lineage: learned positions / parallel blocks / packed QKV
    # (models/families_gpt.py).
    "GPT2LMHeadModel": GPT2LMHeadModel,
    "GPTJForCausalLM": GPTJForCausalLM,
    "GPTBigCodeForCausalLM": GPTBigCodeForCausalLM,
    "OPTForCausalLM": OPTForCausalLM,
    # OPT-shaped decoders: BioGPT (learned positions, gelu, scaled
    # embeddings) and XGLM (fixed sinusoidal positions materialized at
    # load) — models/families_gpt.py.
    "BioGptForCausalLM": BioGptForCausalLM,
    "XGLMForCausalLM": XGLMForCausalLM,
    "MiniCPMForCausalLM": MiniCPMForCausalLM,
    "ExaoneForCausalLM": ExaoneForCausalLM,
    # Llama-math forks with bias/MLP twists (models/families_gpt.py).
    "HeliumForCausalLM": LlamaForCausalLM,
    "Ernie4_5ForCausalLM": Ernie45ForCausalLM,
    "SeedOssForCausalLM": SeedOssForCausalLM,
    "ArceeForCausalLM": ArceeForCausalLM,
    # ALiBi families (slope bias in ops/attention.py).
    "BloomForCausalLM": BloomForCausalLM,
    "MptForCausalLM": MPTForCausalLM,
    "MPTForCausalLM": MPTForCausalLM,
    # Encoder-decoder audio (cross-attention state rows;
    # models/whisper.py + multimodal/audio.py).
    "WhisperForConditionalGeneration": WhisperForConditionalGeneration,
    # Encoder-decoder text (models/bart.py + multimodal/text_encoder.py).
    "BartForConditionalGeneration": BartForConditionalGeneration,
    "BartModel": BartForConditionalGeneration,
    # Encoder-only embedding + cross-encoder families (models/bert.py;
    # reference: the _EMBEDDING_MODELS / _CROSS_ENCODER_MODELS maps of
    # model_executor/models/registry.py).
    "BertModel": BertEmbeddingModel,
    "BertForSequenceClassification": BertForSequenceClassification,
    "RobertaModel": RobertaEmbeddingModel,
    "XLMRobertaModel": RobertaEmbeddingModel,
    "RobertaForSequenceClassification": RobertaForSequenceClassification,
    "XLMRobertaForSequenceClassification": RobertaForSequenceClassification,
}


def resolve_architecture(hf_config) -> type:
    named = list(getattr(hf_config, "architectures", None) or [])
    for arch in named:
        if arch in _REGISTRY:
            return _REGISTRY[arch]
    if named:
        # An architecture that is NAMED and unknown is refused: served
        # as a Llama it would answer, fast and wrong (ROADMAP D15).
        raise ValueError(
            f"no supported architecture in {named} (model_type "
            f"{getattr(hf_config, 'model_type', None)!r}); "
            "supported_architectures() lists what is")
    # Config-shape fallback: a configuration that names no architecture
    # (tiny test configs) is a Llama.
    if hasattr(hf_config, "num_hidden_layers"):
        return LlamaForCausalLM
    raise ValueError("the configuration names no architecture and has "
                     "no num_hidden_layers")


def supported_architectures() -> list[str]:
    return sorted(_REGISTRY)


__all__ = [
    "resolve_architecture",
    "supported_architectures",
    "LlamaArchConfig",
    "LlamaForCausalLM",
]
