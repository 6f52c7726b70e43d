"""gofr_tpu.ops — TPU-first neural net ops.

The compute path of the framework's model-serving datasource. Everything here
is functional, jit-safe, static-shape. Hot ops (attention) have a Pallas TPU
kernel with an XLA reference fallback selected at trace time by platform.

The reference (maohieng/gofr) has no compute ops at all (SURVEY.md §2.9) —
this package exists for the TPU north star (BASELINE.json).
"""

from .attention import (
    chunk_decode_attention,
    chunk_prefill_attention,
    chunk_prefill_why_not_flash,
    decode_attention,
    flash_attention,
    latent_attention,
    latent_chunk_prefill_attention,
    latent_rope_width,
    mha_reference,
    mla_kernel_why_not,
    mla_paged_chunk_decode_attention,
    multi_head_attention,
    paged_chunk_decode_attention,
    paged_gather,
    paged_pool_operand,
    paged_kernel_why_not,
    ring_positions,
)
from .norms import rms_norm
from .rope import apply_rope, rope_frequencies

__all__ = [
    "multi_head_attention",
    "mha_reference",
    "flash_attention",
    "decode_attention",
    "chunk_decode_attention",
    "chunk_prefill_attention",
    "paged_chunk_decode_attention",
    "latent_attention",
    "latent_chunk_prefill_attention",
    "latent_rope_width",
    "mla_kernel_why_not",
    "mla_paged_chunk_decode_attention",
    "paged_gather",
    "paged_pool_operand",
    "paged_kernel_why_not",
    "chunk_prefill_why_not_flash",
    "ring_positions",
    "rms_norm",
    "apply_rope",
    "rope_frequencies",
]
