"""mellum2-12b-a2.5b [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]: a period of
three sliding-window layers (1,024) and one full layer, RoPE theta 500,000
(YaRN on the full layers), 64 experts of width 896 with 8 per token and no
shared expert, an untied vocabulary of 98,304.

``SHARE`` is what one chip holds when eight chips share each layer, expert
parallel: 8 of the 64 experts (the router keeps all 64 outputs), the whole
attention, an eighth of the vocabulary's rows, and one period of 4 layers
(the other 24 layers lie on further pipeline stages).
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=896, vocab_size=98_304,
    rope_theta=500_000.0,
    layer_windows=(1024, 1024, 1024, 0),
    rope_yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
    n_experts=64, top_k=8, experts_held=64,
    tie_embeddings=False,
    microbatches=4,
)

CHIPS_PER_LAYER = 8
SHARE = CONFIG.replace(
    name="mellum2-12b-a2.5b-ep8", n_layers=4,
    experts_held=CONFIG.n_experts // CHIPS_PER_LAYER,
    vocab_size=CONFIG.vocab_size // CHIPS_PER_LAYER,
)

REDUCED = CONFIG.replace(
    name="mellum2-reduced", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=32, vocab_size=97, layer_windows=(8, 8, 8, 0),
    rope_yarn=(4.0, 16, 32.0, 1.0, 1.1386294361119891),
    n_experts=8, top_k=2, experts_held=8, loss_chunk=16, microbatches=2,
)
