"""Fault-tolerant sharded checkpointing.

Layout (one directory per step):

    ckpt_dir/
      step_000120/
        manifest.json          # pytree structure, shapes, dtypes, mesh
        shard_h000.npz         # this host's param/opt shards
        COMMITTED              # written last — atomic commit marker

Writes go to ``step_XXXX.tmp`` and are renamed only after every shard +
manifest lands, so a preemption mid-write can never corrupt the latest
checkpoint; ``latest_step`` ignores uncommitted directories.  Saving is
asynchronous (background thread) — the train loop donates nothing and
keeps stepping while the previous state is serialised.  A failure inside
the background write is captured and re-raised on the next ``wait()`` /
``save()`` instead of dying silently on a daemon thread.

Commit markers guard against *partial* writes; silent bit-rot after
commit (a bad disk, a truncated object-store download) is caught by a
per-shard CRC32 recorded in the manifest and verified on ``restore``.
``restore_latest`` walks back to the newest step that verifies, so one
corrupt checkpoint costs re-training from the previous one — not the
job.

Elastic restore: arrays are stored logically-whole per host shard with
their global offsets; ``repro.distributed.elastic`` re-stitches them for
a different mesh/host count.
"""
from __future__ import annotations

import json
import logging
import os
import shutil
import threading
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

logger = logging.getLogger(__name__)


class CheckpointCorruptionError(RuntimeError):
    """A committed checkpoint failed CRC verification on restore."""


def _flatten(tree: Any) -> tuple[list[tuple[str, Any]], Any]:
    leaves, treedef = jax.tree.flatten_with_path(tree)
    named = [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path), leaf) for path, leaf in leaves]
    return named, treedef


@dataclass
class CheckpointManager:
    directory: str | Path
    host_id: int = 0
    n_hosts: int = 1
    keep: int = 3
    _thread: Optional[threading.Thread] = field(default=None, repr=False)
    _error: Optional[BaseException] = field(default=None, repr=False)

    def __post_init__(self):
        self.directory = Path(self.directory)
        self.directory.mkdir(parents=True, exist_ok=True)

    # -- save ------------------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = False) -> None:
        # Pull device shards to host memory synchronously (cheap copy),
        # serialise + fsync in the background.  bfloat16 has no native
        # numpy storage — persist as uint16 bits + a dtype tag.
        named, _ = _flatten(tree)
        host_named = []
        bf16_keys = []
        for k, v in named:
            arr = np.asarray(v)
            if arr.dtype.name == "bfloat16":
                arr = arr.view(np.uint16)
                bf16_keys.append(k)
            host_named.append((k, arr))
        self.wait()

        def write():
            tmp = self.directory / f"step_{step:06d}.tmp"
            final = self.directory / f"step_{step:06d}"
            tmp.mkdir(parents=True, exist_ok=True)
            shard_name = f"shard_h{self.host_id:03d}.npz"
            np.savez(tmp / shard_name, **dict(host_named))
            crc32 = {shard_name: zlib.crc32((tmp / shard_name).read_bytes())}
            if (final / "manifest.json").exists():
                # Another host committed this step first: carry its shard
                # CRCs forward so ours don't clobber them.
                prev = json.loads((final / "manifest.json").read_text())
                crc32 = {**prev.get("crc32", {}), **crc32}
            manifest = {
                "step": step,
                "n_hosts": self.n_hosts,
                "keys": [k for k, _ in host_named],
                "shapes": {k: list(v.shape) for k, v in host_named},
                "dtypes": {k: str(v.dtype) for k, v in host_named},
                "bf16_keys": bf16_keys,
                "crc32": crc32,
                "time": time.time(),
            }
            (tmp / "manifest.json").write_text(json.dumps(manifest))
            (tmp / "COMMITTED").touch()
            if final.exists():
                # Another host already committed this step: merge our
                # shard + manifest into the shared directory.
                for f in tmp.iterdir():
                    os.replace(f, final / f.name)
                shutil.rmtree(tmp, ignore_errors=True)
            else:
                os.replace(tmp, final)
            self._gc()

        def guarded_write():
            try:
                write()
            except BaseException as e:   # surfaced on wait()/next save()
                self._error = e

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=guarded_write,
                                            daemon=True)
            self._thread.start()

    def wait(self) -> None:
        """Join the in-flight background save.  A failure captured on the
        writer thread is re-raised *here* (and from the next ``save()``,
        which waits first) — an async save error must not be silent."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self.directory / f"step_{s:06d}",
                          ignore_errors=True)

    # -- restore ---------------------------------------------------------------
    def steps(self) -> list[int]:
        out = []
        for p in sorted(self.directory.glob("step_*")):
            if p.suffix == ".tmp" or not (p / "COMMITTED").exists():
                continue
            out.append(int(p.name.split("_")[1]))
        return out

    def latest_step(self) -> Optional[int]:
        steps = self.steps()
        return steps[-1] if steps else None

    def restore(self, step: int, like: Any) -> Any:
        """Restore into the structure of ``like`` (shapes must match).

        The host shard's CRC32 is verified against the manifest before
        deserialising; a mismatch raises
        :class:`CheckpointCorruptionError` (post-commit bit-rot — the
        atomic-commit marker cannot catch it)."""
        import ml_dtypes
        d = self.directory / f"step_{step:06d}"
        manifest = json.loads((d / "manifest.json").read_text())
        bf16 = set(manifest.get("bf16_keys", ()))
        shard_name = f"shard_h{self.host_id:03d}.npz"
        expect = manifest.get("crc32", {}).get(shard_name)
        if expect is not None:
            got = zlib.crc32((d / shard_name).read_bytes())
            if got != expect:
                raise CheckpointCorruptionError(
                    f"step {step}: {shard_name} crc32 {got:#010x} != "
                    f"manifest {expect:#010x} (corrupt shard)")
        data = np.load(d / shard_name)
        named, treedef = _flatten(like)
        leaves = []
        for key, ref in named:
            arr = data[key]
            if key in bf16:
                arr = arr.view(ml_dtypes.bfloat16)
            if tuple(arr.shape) != tuple(ref.shape):
                raise ValueError(
                    f"{key}: checkpoint shape {arr.shape} != {ref.shape}; "
                    "use repro.distributed.elastic.reshard_checkpoint")
            leaves.append(jax.device_put(arr).astype(ref.dtype) if hasattr(
                ref, "dtype") else arr)
        return jax.tree.unflatten(treedef, leaves)

    def restore_latest(self, like: Any) -> tuple[Optional[int], Any]:
        """Restore the newest committed step that *verifies*.  A step
        failing CRC (or deserialisation) is skipped with a warning and
        the previous committed step is tried — one corrupt checkpoint
        costs re-training from the prior one, not the job.  Raises only
        when every committed step fails."""
        steps = self.steps()
        if not steps:
            return None, like
        last_err: Optional[BaseException] = None
        for step in reversed(steps):
            try:
                return step, self.restore(step, like)
            except (CheckpointCorruptionError, OSError,
                    ValueError, KeyError) as e:
                logger.warning(
                    "checkpoint step %d failed to restore (%s); falling "
                    "back to previous committed step", step, e)
                last_err = e
        raise CheckpointCorruptionError(
            f"no committed step in {self.directory} restored cleanly "
            f"(tried {steps[::-1]})") from last_err
