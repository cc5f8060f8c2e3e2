"""Shared fixtures.  NOTE: no XLA_FLAGS manipulation here — smoke tests and
benches must see the single real CPU device; only launch/dryrun.py (run as a
separate process) forces the 512-device host platform."""
import numpy as np
import pytest

from repro.core import EmbeddingSimilarity, SearchParams
from repro.data import make_collection, make_embeddings


@pytest.fixture(scope="session")
def small_world():
    """A small repository + clustered embeddings shared across tests."""
    coll = make_collection(num_sets=120, vocab_size=800, avg_size=8,
                           max_size=24, zipf_a=1.1, seed=7)
    emb = make_embeddings(800, dim=16, cluster_size=4.0, seed=7)
    return coll, EmbeddingSimilarity(emb)


@pytest.fixture(scope="session")
def default_params():
    return SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)


@pytest.fixture(scope="session")
def dblp_world():
    """A DBLP-shaped repository: medium-to-large sets (average ~60, largest
    260, six sets over 256), so the cover rule pads candidates to 384
    where a power of two would pad to 512."""
    coll = make_collection(num_sets=300, vocab_size=3000, avg_size=60,
                           max_size=260, zipf_a=1.05, seed=0)
    emb = make_embeddings(3000, dim=16, cluster_size=4.0, seed=0)
    return coll, EmbeddingSimilarity(emb)
