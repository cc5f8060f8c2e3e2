"""Public wrappers for the Pallas kernels.

Every wrapper compiles its kernel for the accelerator unless the caller
passes ``interpret=True``, which runs the kernel body as traced jnp on
any backend (how tests/test_kernels.py checks each kernel against its
``ref.py`` oracle on a CPU).  The backend never picks interpret mode: a
compiled call off-TPU fails loudly instead of quietly running something
other than what the chip would run.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .auction_round import auction_topk2 as _auction_topk2
from .cosine_topk import cosine_topk as _cosine_topk
from .flash_attention import flash_attention as _flash_attention
from .refine_events import refine_events as _refine_events
from .ssd_scan import ssd_chunked as _ssd_chunked


def cosine_topk(qe, ev, k: int, bv: int = 512, interpret: bool = False):
    """Blocked cosine top-k (token-stream generator).  See cosine_topk.py."""
    return _cosine_topk(jnp.asarray(qe), jnp.asarray(ev), k=k, bv=bv,
                        interpret=interpret)


def refine_events(state, c_set, c_q, c_slot, c_sim, interpret: bool = False):
    """Set-segmented admission of one lane-packed refinement chunk with a
    VMEM-resident carry (interpret mode only).  See refine_events.py."""
    return _refine_events(state, jnp.asarray(c_set), jnp.asarray(c_q),
                          jnp.asarray(c_slot), jnp.asarray(c_sim),
                          interpret=interpret)


def auction_topk2(wm, prices, bn: int = 256, interpret: bool = False):
    """Fused profit top-2 for one auction round.  See auction_round.py."""
    return _auction_topk2(jnp.asarray(wm), jnp.asarray(prices), bn=bn,
                          interpret=interpret)


def ssd(x, dt, A, B, C, D, chunk: int = 64, interpret: bool = False):
    """Mamba2 SSD chunked scan; pads L to a multiple of ``chunk``."""
    x = jnp.asarray(x)
    L = x.shape[1]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(jnp.asarray(dt), ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(jnp.asarray(B), ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(jnp.asarray(C), ((0, 0), (0, pad), (0, 0), (0, 0)))
    y = _ssd_chunked(x, jnp.asarray(dt), jnp.asarray(A), jnp.asarray(B),
                     jnp.asarray(C), jnp.asarray(D), chunk=chunk,
                     interpret=interpret)
    return y[:, :L]


def flash_attention(q, k, v, bq: int = 256, bk: int = 256,
                    causal: bool = True, interpret: bool = False):
    """Causal flash attention (serving path).  See flash_attention.py."""
    return _flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            bq=bq, bk=bk, causal=causal,
                            interpret=interpret)


# re-exported oracles (benchmarks compare against these)
cosine_topk_ref = ref.cosine_topk_ref
refine_events_packed_ref = ref.refine_events_packed_ref
auction_topk2_ref = ref.auction_topk2_ref
ssd_ref = ref.ssd_ref
flash_attention_ref = ref.flash_attention_ref
