"""Whole decode step's share of the chip's roofline (%), for the latent
attention and expert cell: for each traced step the larger of the FLOPs it
needs over the bf16 peak and the bytes it needs (every weight once,
the held experts included, and the live latent rows at the sequences'
real lengths; ``bench/flops_mla_moe.py``) over the HBM bandwidth, summed,
over the traced window."""

from bench.metrics.mfu_serve import read  # noqa: F401
