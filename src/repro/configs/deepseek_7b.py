"""DeepSeek-7B: dense llama-arch [arXiv:2401.02954]."""
from .base import ENGRAM_27B, ModelConfig, engram_for, register


@register("deepseek-7b")
def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-7b",
        family="dense",
        n_layers=30,
        d_model=4096,
        vocab_size=102_400,
        n_heads=32,
        n_kv_heads=32,
        head_dim=128,
        d_ff=11008,
        engram=engram_for(30, ENGRAM_27B),
        rope_theta=10_000.0,
    )


ONE_CHIP_SOURCE = ("DeepSeek LLM 7B base, arXiv 2401.02954 Table 2 "
                   "(widths); Engram-27B tables, paper §5.2 (16 tables x "
                   "2,262,400 rows x 160 bf16 lanes per Engram layer)")
# cut -> (value held on one chip, published value)
ONE_CHIP_REDUCED = {
    "n_layers": (12, 30),
    "table_vocab": (262_144, 2_262_400),
}


@register("deepseek-7b-1chip")
def one_chip() -> ModelConfig:
    """deepseek-7b at its published widths, served from one 16 GB TPU v5e.

    Every width is the published one (d_model 4096, 32 x 128 heads, 32 KV
    heads, d_ff 11008, vocab 102,400, rope 10,000, bf16) and so are the
    Engram-27B segment shapes (8 heads per order, orders (2, 3), emb_dim
    1280: 160 x bf16 = 320 B a segment). Two things are cut
    (``ONE_CHIP_REDUCED``):

    * depth, 30 -> 12 layers, with Engram layers ``engram_for(12)`` =
      (2, 5);
    * rows held per table, 2,262,400 -> 2^18: one chip's share of the
      pooled table (paper: the table lives in a pool and each device
      holds a share). n-grams hash into the held rows, so per-token
      traffic and row shape are unchanged.

    HBM: ~3.3 B weight params (6.6 GB bf16), two Engram layers of 16 x
    2^18 x 256 lane-padded bf16 lanes (2.1 GB each; the 160-lane rows take
    the same on the chip's tiled layout), and a 16-slot x 1,024-position
    KV cache (3.2 GB): ~14.1 GB of the chip's 16 GB. ``strategy`` is
    ``"local"``: one chip holds the whole cut table.
    """
    return ModelConfig(
        name="deepseek-7b-1chip",
        family="dense",
        n_layers=12,
        d_model=4096,
        vocab_size=102_400,
        n_heads=32,
        n_kv_heads=32,
        head_dim=128,
        d_ff=11008,
        engram=engram_for(12, dict(ENGRAM_27B, table_vocab=262_144),
                          strategy="local"),
        rope_theta=10_000.0,
    )


def reduced() -> ModelConfig:
    from .base import EngramConfig
    return ModelConfig(
        name="deepseek-7b-reduced",
        family="dense",
        n_layers=4,
        d_model=64,
        vocab_size=521,
        n_heads=4,
        n_kv_heads=4,
        head_dim=16,
        d_ff=160,
        engram=EngramConfig(table_vocab=2048, emb_dim=32, n_heads=4,
                            orders=(2, 3), layers=(1, 2), strategy="local"),
        dtype="float32",
    )
