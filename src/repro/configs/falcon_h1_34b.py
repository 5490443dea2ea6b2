"""falcon-h1-34b [hybrid] 72L d_model=5120 20H (GQA kv=4) d_ff=21504
vocab=261120.  Every layer runs attention heads and Mamba-2 heads (32 x
128, state 256, 2 groups) in parallel on one normed input and sums them
into the residual.  The muP multipliers of the published config are
folded into the weights they multiply, so the program has none.
[hf:tiiuae/Falcon-H1-34B-Instruct; arXiv:2507.22448]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="falcon-h1-34b", family="hybrid",
    n_layers=72, d_model=5120, n_heads=20, n_kv_heads=4, head_dim=128,
    d_ff=21504, vocab_size=261120, rope_theta=1e11, norm_eps=1e-5,
    ssm_state=256, ssm_conv=4, ssm_heads=32, ssm_head_dim=128,
    ssm_groups=2, ssm_chunk=128, hybrid_fusion="sum", activation="silu",
)
