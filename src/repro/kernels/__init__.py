"""Pallas TPU kernels for the paper's compute hot spots (DESIGN.md §7):

  cosine_topk     — blocked cosine similarity + running top-k (token stream)
  auction_topk2   — fused profit top-2 (auction verification round)
  refine_events   — set-segmented greedy admission of a refinement chunk
                    (VMEM-resident carry, lane-packed levels; interpret
                    mode only — Mosaic refuses it, see refine_events.py)
  ssd             — Mamba2 SSD chunked scan (ssm/hybrid architectures)
  flash_attention — causal online-softmax attention (serving/prefill path)

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd wrapper
in ``ops.py``; interpret mode runs only where the caller passes
``interpret=True``.
"""
from .ops import (auction_topk2, auction_topk2_ref, cosine_topk,
                  cosine_topk_ref, flash_attention, flash_attention_ref,
                  refine_events, refine_events_packed_ref, ssd, ssd_ref)

__all__ = ["cosine_topk", "cosine_topk_ref", "auction_topk2",
           "auction_topk2_ref", "refine_events", "refine_events_packed_ref",
           "ssd", "ssd_ref", "flash_attention", "flash_attention_ref"]
