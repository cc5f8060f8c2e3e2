"""Rolling checkpoint manager: step-numbered checkpoints + metadata,
restore-latest, retention, preemption safety (restart resumes mid-run)."""
from __future__ import annotations

import json
import os
import re
from typing import Any, Optional

from .checkpoint import AsyncSaver, restore, save

# new checkpoints are .zst; .msgpack (raw, written by older versions) is
# still discovered and restored
_PAT = re.compile(r"ckpt_(\d+)\.(zst|msgpack)$")
_SUFFIXES = ("zst", "msgpack")


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._saver = AsyncSaver() if async_save else None

    def _path(self, step: int) -> str:
        """Path a new checkpoint for ``step`` will be written to."""
        return os.path.join(self.dir, f"ckpt_{step:09d}.zst")

    def _step_paths(self, step: int):
        """Existing checkpoint files for ``step`` (any codec)."""
        return [p for suffix in _SUFFIXES
                if os.path.exists(p := os.path.join(
                    self.dir, f"ckpt_{step:09d}.{suffix}"))]

    def _find_path(self, step: int):
        """Checkpoint file to restore for ``step``.

        A directory can hold the same step under both codecs (a raw one
        from an older version); the newest write wins."""
        paths = self._step_paths(step)
        if not paths:
            return None
        return max(paths, key=os.path.getmtime)

    def steps(self):
        out = set()
        for f in os.listdir(self.dir):
            m = _PAT.match(f)
            if m:
                out.add(int(m.group(1)))      # dedupe mixed-codec dirs
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def save(self, step: int, tree: Any, metadata: Optional[dict] = None):
        meta = dict(metadata or {})
        meta["step"] = step
        meta.setdefault("codec", "zstd")
        payload = {"meta": meta, "state": tree}
        if self._saver is not None:
            self._saver.save(self._path(step), payload)
        else:
            save(self._path(step), payload)
        self._gc()

    def restore_latest(self):
        """Returns (step, state, meta) or None."""
        step = self.latest_step()
        if step is None:
            return None
        self.wait()
        payload = restore(self._find_path(step))
        return step, payload["state"], payload["meta"]

    def wait(self):
        if self._saver is not None:
            self._saver.wait()

    def _gc(self):
        steps = self.steps()
        for s in steps[:-self.keep]:
            for p in self._step_paths(s):
                try:
                    os.unlink(p)
                except OSError:
                    pass
