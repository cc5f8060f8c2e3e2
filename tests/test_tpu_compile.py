"""The main path's device programs compile for a TPU v5e chip.

Each test compiles for a described (not attached) v5e chip at the widths
the full-scale Twitter deployment uses (``chip_smoke.py``: 10 shards of
~2,721 sets, max set 151, 300-d embeddings over a 72,910-token vocab), so
a kernel or program Mosaic/XLA would refuse fails here and not first on
the chip.  The topology is described inside a fixture and the persistent
compilation cache is off around these compiles: entries written for a
described chip cannot be read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

NUM_SETS, TOTAL_SLOTS, C_PAD = 2721, 61504, 256      # one Twitter shard
VOCAB, DIM, NQ_PAD, VB = 72910, 300, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                          sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_compact_indices_vmapped_over_wave_batch(shape):
    from repro.core.wave import compact_indices

    _compile(jax.vmap(compact_indices), shape((8, NUM_SETS), jnp.bool_))


def test_auction_topk2_at_solver_widths(shape):
    """The wave's auction rounds run the kernel per (K, K) padded problem
    (K = pow2 of the largest set), vmapped over the round batch."""
    from repro.kernels import auction_topk2

    kern = jax.vmap(lambda w, p: auction_topk2(w, p, bn=C_PAD))
    text = _compile(kern, shape((VB, C_PAD, C_PAD)),
                    shape((VB, C_PAD))).as_text()
    assert "tpu_custom_call" in text


def test_cosine_topk_at_stream_widths(shape):
    from repro.kernels import cosine_topk

    text = _compile(lambda q, e: cosine_topk(q, e, k=128, bv=512),
                    shape((NQ_PAD, DIM)), shape((VOCAB, DIM))).as_text()
    assert "tpu_custom_call" in text


def test_fused_wave_program_compiles(shape):
    """One fused wave (auction verifier, so the compiled auction kernel
    is inside) at a Twitter shard's widths."""
    from repro.core.matching.auction import make_eps_schedule
    from repro.core.wave import _WAVE_VB_CAP, WaveConfig, _wave_fn

    B, T, n_chunks = 1, 1024, 1
    cfg = WaveConfig(num_sets=NUM_SETS, total_slots=TOTAL_SLOTS, q_words=8,
                     k=10, n_chunks=n_chunks, chunk=256, n_tuples=T,
                     nq_pad=NQ_PAD, c_pad=C_PAD, B=B,
                     verify_batch=_WAVE_VB_CAP, rounds=2, ub_mode="sound",
                     verifier="auction", refine_layout="segmented",
                     alpha=0.8, use_kernel=True)
    i32, f32 = jnp.int32, jnp.float32
    n_eps = len(np.asarray(make_eps_schedule(1e-4)))
    args = (shape((B, T), i32), shape((B, T), i32), shape((B, T), f32),
            shape((B, NQ_PAD), i32), shape((B,), i32), shape((B,), f32),
            shape((VOCAB, DIM), f32), shape((NUM_SETS, C_PAD), i32),
            shape((NUM_SETS,), i32), shape((n_eps,), f32),
            shape((VOCAB + 1,), i32), shape((TOTAL_SLOTS + 1,), i32),
            shape((TOTAL_SLOTS + 1,), i32))
    text = _wave_fn(cfg).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_verifier_weight_program_then_solver_compile(shape):
    """The host continuation's solver batch at a closed cohort's widths
    (32 queries x a verify batch of 32 rows): the weight program, then
    the exact solver on the device array it returns."""
    from repro.core.matching.hungarian import hungarian_batch
    from repro.core.similarity import cosine_row_blocks, device_weights

    B, nq_pad, i32 = 1024, 32, jnp.int32
    w = device_weights.lower(
        cosine_row_blocks, shape((VOCAB, DIM)), shape((B, nq_pad), i32),
        shape((B, C_PAD), i32), shape((B,), i32), shape((B,), i32),
        shape((), jnp.float32)).compile()
    assert w.as_text()
    hungarian_batch.lower(shape((B, nq_pad, C_PAD)), shape((B,), i32),
                          shape((B,), i32)).compile()


def test_rectangular_solver_compiles_at_dblp_widths(shape):
    """The exact solver on its (R, max(R, C)) rectangle at DBLP's widths:
    256-row blocks of 256 query rows against the 640 cover of a 514-token
    largest set, and the weight program that feeds it."""
    from repro.core.matching.hungarian import hungarian_batch
    from repro.core.similarity import cosine_row_blocks, device_weights
    from repro.core.types import lane_cover

    B, nq_pad, c_pad, i32 = 256, 256, lane_cover(514), jnp.int32
    assert c_pad == 640
    device_weights.lower(
        cosine_row_blocks, shape((25159, DIM)), shape((B, nq_pad), i32),
        shape((B, c_pad), i32), shape((B,), i32), shape((B,), i32),
        shape((), jnp.float32)).compile()
    text = hungarian_batch.lower(shape((B, nq_pad, c_pad)), shape((B,), i32),
                                 shape((B,), i32)).compile().as_text()
    assert "f32[256,256,640]" in text
    assert "1024,1024" not in text and "640,640" not in text


def test_fused_wave_compiles_at_dblp_keys(shape):
    """One fused wave (Hungarian rounds) at a DBLP shard's widths and the
    keys of a cohort of 16 with a 514-token query: query rows and
    candidates both pad to the 640 cover, so the rounds solve 16 x 16
    blocks of 640 x 640, and no solver tensor has a 1024 axis."""
    from repro.core.matching.auction import make_eps_schedule
    from repro.core.types import lane_cover
    from repro.core.wave import WaveConfig, _wave_fn, cohort_pads

    num_sets, total_slots, vocab = 425, 73_360, 25_159     # one DBLP shard
    B, T, n_chunks = 16, 4096, 128
    _, nq_pad, q_words = cohort_pads([np.zeros(514, np.int32)],
                                     [np.zeros(T - 1)])
    assert nq_pad == lane_cover(514) == 640 and q_words == 32
    cfg = WaveConfig(num_sets=num_sets, total_slots=total_slots,
                     q_words=q_words, k=10, n_chunks=n_chunks, chunk=256,
                     n_tuples=T, nq_pad=nq_pad, c_pad=640, B=B,
                     verify_batch=16, rounds=2, ub_mode="sound",
                     verifier="hungarian", refine_layout="segmented",
                     alpha=0.8, use_kernel=True)
    i32, f32 = jnp.int32, jnp.float32
    n_eps = len(np.asarray(make_eps_schedule(1e-4)))
    args = (shape((B, T), i32), shape((B, T), i32), shape((B, T), f32),
            shape((B, nq_pad), i32), shape((B,), i32), shape((B,), f32),
            shape((vocab, DIM), f32), shape((num_sets, 640), i32),
            shape((num_sets,), i32), shape((n_eps,), f32),
            shape((vocab + 1,), i32), shape((total_slots + 1,), i32),
            shape((total_slots + 1,), i32))
    text = _wave_fn(cfg).lower(*args).compile().as_text()
    assert "f32[16,16,640,640]" in text          # B x vb cost blocks
    assert "[16,16,1024" not in text
