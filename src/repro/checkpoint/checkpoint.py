"""Pytree checkpointing: zstd-compressed msgpack, atomic, async-capable.

Layout-agnostic: arrays are serialized host-side (device_get) with dtype
(incl. bfloat16 via ml_dtypes) and shape; restore returns numpy arrays that
``jax.device_put``/``NamedSharding`` reshard onto whatever mesh the restart
uses — this is what makes elastic re-mesh restarts work (runtime/fault.py):
a checkpoint written on a (2,16,16) mesh restores onto any other mesh.

Checkpoints are written zstd-compressed.  ``restore`` sniffs the zstd
frame magic, so raw-msgpack checkpoints written by older versions still
restore."""
from __future__ import annotations

import io
import os
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor, Future
from typing import Any, Optional

import jax
import msgpack
import numpy as np
import zstandard as zstd

# First bytes of every zstd frame (RFC 8878) — msgpack maps never start
# with this, so the on-disk codec is sniffable without a side channel.
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"

try:
    import ml_dtypes
    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BF16 = None


def _encode_dtype(dt: np.dtype) -> str:
    return dt.name


def _decode_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        assert _BF16 is not None, "bfloat16 checkpoint needs ml_dtypes"
        return _BF16
    return np.dtype(name)


def _pack(obj):
    if isinstance(obj, dict):
        return {"t": "d", "v": {k: _pack(v) for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"t": "l" if isinstance(obj, list) else "tu",
                "v": [_pack(v) for v in obj]}
    if obj is None:
        return {"t": "n"}
    if isinstance(obj, (int, float, str, bool)):
        return {"t": "s", "v": obj}
    arr = np.asarray(obj)
    return {"t": "a", "dtype": _encode_dtype(arr.dtype),
            "shape": list(arr.shape), "data": arr.tobytes()}


def _unpack(obj):
    t = obj["t"]
    if t == "d":
        return {k: _unpack(v) for k, v in obj["v"].items()}
    if t == "l":
        return [_unpack(v) for v in obj["v"]]
    if t == "tu":
        return tuple(_unpack(v) for v in obj["v"])
    if t == "n":
        return None
    if t == "s":
        return obj["v"]
    dt = _decode_dtype(obj["dtype"])
    return np.frombuffer(obj["data"], dtype=dt).reshape(obj["shape"])


def _to_host(x):
    if isinstance(x, (str, bool, int, float)) or x is None:
        return x
    return np.asarray(jax.device_get(x))


def save(path: str, tree: Any, *, level: int = 3) -> None:
    """Atomic synchronous save (tmp file + rename), zstd-compressed."""
    host_tree = jax.tree_util.tree_map(_to_host, tree)
    payload = msgpack.packb(_pack(host_tree), use_bin_type=True)
    comp = zstd.ZstdCompressor(level=level).compress(payload)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(comp)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def restore(path: str) -> Any:
    with open(path, "rb") as f:
        comp = f.read()
    if comp[:4] == _ZSTD_MAGIC:
        payload = zstd.ZstdDecompressor().decompress(comp)
    else:
        payload = comp
    return _unpack(msgpack.unpackb(payload, raw=False))


class AsyncSaver:
    """Snapshot on the caller thread (cheap device_get), write off-thread —
    checkpointing off the training critical path (DESIGN.md §5)."""

    def __init__(self):
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._last: Optional[Future] = None

    def save(self, path: str, tree: Any) -> Future:
        self.wait()
        host_tree = jax.tree_util.tree_map(_to_host, tree)
        self._last = self._pool.submit(save, path, host_tree)
        return self._last

    def wait(self):
        if self._last is not None:
            self._last.result()
            self._last = None
