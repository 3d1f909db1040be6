"""granite-4.0-h-micro — interleaved 40L d_model=2048: 36 Mamba-2 layers
(64 heads of 64, d_state 128, expand 2, 1 group, conv 4 with bias) and 4
GQA attention layers (32H, kv=8, head_dim 64, no positional encoding) at
5, 15, 25, 35; a gated SiLU MLP of 8192 after every mixer; vocab 100352,
tied embeddings [hf:ibm-granite/granite-4.0-h-micro]. The muP scalars
(embedding 12, attention 1/64, residual 0.22, logits 8) are not modelled:
attention scales by 1/sqrt(64)."""
from .common import ModelConfig, SSMConfig, smoke_of

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="interleaved",
    n_layers=40, d_model=2048, n_heads=32, n_kv=8, d_ff=8192, vocab=100352,
    head_dim=64, rope=False, tie_embeddings=True, layer_types=_PERIOD * 4,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, d_conv=4, n_groups=1,
                  chunk=256),
)
SMOKE = smoke_of(CONFIG, n_layers=4, n_heads=4, n_kv=2,
                 layer_types=("mamba", "attention", "mamba", "mamba"))
