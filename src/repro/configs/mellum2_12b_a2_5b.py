"""mellum2-12b-a2.5b [moe] — sliding and full attention in one stack, 64
fine-grained experts top-8.
[https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json]

28 layers of (sliding, sliding, sliding, full) attention, GQA 32/4 at
head_dim 128; sliding layers see a 1,024-token window under default RoPE,
full layers are causal under YaRN (factor 16 over 8,192 positions). Every
layer's MLP is sparse: 64 SwiGLU experts of width 896, top-8 with the
kept probabilities renormalised, no shared expert.

Assumed, since the config gives no key for them: no q/k RMSNorm; a
softmax router, then top-8, then renormalise (``norm_topk_prob``); a
Switch load-balance term over all 64 experts, coefficient 0.001; the MTP
head named in the model's description is left out.
"""
from repro.configs import base
from repro.configs.base import AttentionKind, ModelConfig, RopeConfig

SLIDING = AttentionKind(window=1024, rope=RopeConfig(theta=500_000.0))
FULL = AttentionKind(window=None, rope=RopeConfig(
    theta=500_000.0, kind="yarn", factor=16.0, original_max_positions=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782))
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,                        # moe_intermediate_size
    vocab_size=98_304,
    rope_theta=500_000.0,
    num_experts=64,
    experts_per_token=8,
    experts_held=64,
    router_aux_coef=0.001,
    block_pattern=PERIOD * 7,
    attention_kinds=(("sliding_attention", SLIDING),
                     ("full_attention", FULL)),
    dtype="float32",
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
)


def chip_share(chips: int = 8, chip: int = 0) -> ModelConfig:
    """Chip ``chip``'s share of a deployment that divides every layer over
    ``chips`` chips, over one whole period of the layer pattern: at 8
    chips, 8 of the 64 experts (the router keeps all 64 outputs and its
    top-8), 4 of the 32 query heads with 1 of the 4 key/value heads, and
    12,288 of the 98,304 vocabulary rows; 267,211,008 parameters."""
    return base.chip_share(CONFIG, chips, chip, len(PERIOD))
