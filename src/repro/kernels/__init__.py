"""Pallas TPU kernels for the framework's compute hot-spots.

Kernels (each with a pure-jnp oracle in ref.py, wrapper in ops.py):
  * flash_attention — causal GQA attention (models' attn_impl="flash")
  * ssd             — Mamba2 SSD intra-chunk quadratic form
  * rmsnorm         — fused normalisation
  * stencil         — 2-D stencil (MGMark SC, Adjacent-Access pattern)
  * bitonic         — bitonic compare-exchange stage (MGMark BS, Irregular)

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling,
128-aligned MXU shapes).  Tests validate them on CPU in interpret mode;
``chip_smoke.py`` runs them Mosaic-compiled on the chip, and
tests/test_tpu_compile.py compiles them for a described v5e.
"""
from . import ops, ref
from .ops import (flash_attention, rmsnorm, ssd_chunk_kernel, ssd_pallas,
                  stencil2d, bitonic_stage)

__all__ = ["ops", "ref", "flash_attention", "rmsnorm", "ssd_chunk_kernel",
           "ssd_pallas", "stencil2d", "bitonic_stage"]
