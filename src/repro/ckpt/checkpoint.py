"""Checkpointing: sharded, async-capable, elastic-restore.

Layout (one directory per step):
    ckpt_dir/step_000100/
        manifest.json        # step, tree structure, shapes/dtypes, mesh info
        shard_<host>.npz     # this host's addressable shard data

Design points for the 1000+-node story:
  * every host writes only its addressable shards (no gather to host 0);
  * restore re-shards to whatever mesh is active — a job restarted on a
    different topology (elastic scaling) reassembles from the manifest;
  * `save_async` runs serialization off-thread so the train loop overlaps
    checkpoint I/O with compute;
  * integrity: manifest written last (atomic rename) — a crash mid-write
    leaves no valid-looking checkpoint; `latest_step` only trusts manifests.
    Each shard's CRC32 rides in the manifest, `validate_step` recomputes
    it, and `latest_step` skips a step whose shards fail validation
    (falling back to the newest earlier valid step with a warning) instead
    of letting resume crash mid-restore on an opaque npz error.  `restore`
    re-checks before reading and raises the typed `CheckpointCorrupt`.

Fault injection: `save` consults `repro.faults` (the ambient
``REPRO_GA_FAULTS`` injector, or one passed via ``faults=``) at the
``ckpt_corrupt`` site — when armed, it flips bytes in the just-written
shard AFTER its checksum was recorded, simulating bit-rot the validation
path must catch.

On this single-host container each "host" is host 0; the pathing and
manifest format are multi-host from day one.
"""

from __future__ import annotations

import dataclasses
import io
import json
import os
import shutil
import threading
import time
import warnings
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro import faults as FLT

_SEP = "/"


class CheckpointCorrupt(RuntimeError):
    """A checkpoint step failed shard-checksum validation."""


def _flatten(tree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        flat[key] = leaf
    return flat


def _crc32_file(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            crc = zlib.crc32(block, crc)
    return crc


def save(ckpt_dir: str, step: int, tree, extra: Optional[Dict] = None,
         host_id: int = 0, *, faults=None, fault_tag: str = "") -> str:
    """Synchronous sharded save. Returns the checkpoint path.

    Each shard's CRC32 + byte count land in the manifest so readers can
    validate before trusting the step.  `faults`/`fault_tag` hook the
    ``ckpt_corrupt`` injection site (see `repro.faults`): when a rule
    fires, the shard is corrupted AFTER its checksum was recorded."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    flat = _flatten(tree)
    # one device_get of every leaf: their copies to the host overlap, so
    # the save waits one round trip, not one per leaf
    host = jax.device_get(list(flat.values()))
    arrays, meta = {}, {}
    for k, v in zip(flat, host):
        arr = np.asarray(v)
        logical_dtype = str(arr.dtype)
        if logical_dtype not in ("float64", "float32", "float16", "int64",
                                 "int32", "int16", "int8", "uint64", "uint32",
                                 "uint16", "uint8", "bool"):
            # ml_dtypes (bfloat16, fp8...) — store the raw bytes
            arr = arr.view(np.uint8 if arr.dtype.itemsize == 1 else np.uint16)
        arrays[k.replace(_SEP, "__")] = arr
        meta[k] = {"shape": list(arr.shape), "dtype": logical_dtype}
    shard_name = f"shard_{host_id}.npz"
    shard_path = os.path.join(tmp, shard_name)
    # the npz is built in memory: its checksum comes from the bytes written,
    # with no second read of the file
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    data = buf.getbuffer()
    with open(shard_path, "wb") as f:
        f.write(data)
    shards = {shard_name: {"crc32": zlib.crc32(data), "bytes": len(data)}}
    injector = FLT.resolve_faults(faults)
    if injector is not None:
        rule = injector.fires("ckpt_corrupt",
                              tag=f"{fault_tag}|{ckpt_dir}|step={step}")
        if rule is not None:   # bit-rot AFTER the checksum: readers must catch
            FLT.corrupt_file(shard_path, seed=rule.seed)
    manifest = {"step": step, "keys": meta, "extra": extra or {},
                "n_hosts": 1, "time": time.time(), "shards": shards}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)
    return path


class AsyncCheckpointer:
    """Overlap checkpoint serialization with training compute."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[str] = None

    def save(self, ckpt_dir: str, step: int, tree, extra=None):
        self.wait()
        # device_get on the main thread (cheap on CPU; on TPU this is the
        # D2H copy we want off the critical path — but values must be
        # snapshotted before the optimizer mutates them).
        host_tree = jax.tree.map(np.asarray, jax.device_get(tree))

        def work():
            self.last_path = save(ckpt_dir, step, host_tree, extra)

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def validate_step(ckpt_dir: str, step: int) -> Optional[str]:
    """None when the step's shards match their manifest checksums, else a
    human-readable reason.  Manifests written before checksums existed
    (no "shards" key) validate trivially — they can't be checked."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return f"unreadable manifest: {e}"
    for shard_name, meta in (manifest.get("shards") or {}).items():
        shard_path = os.path.join(path, shard_name)
        if not os.path.exists(shard_path):
            return f"missing shard {shard_name}"
        if os.path.getsize(shard_path) != int(meta["bytes"]):
            return (f"shard {shard_name} is {os.path.getsize(shard_path)} "
                    f"bytes, manifest says {meta['bytes']}")
        crc = _crc32_file(shard_path)
        if crc != int(meta["crc32"]):
            return (f"shard {shard_name} checksum {crc:#010x} != manifest "
                    f"{int(meta['crc32']):#010x}")
    return None


def latest_step(ckpt_dir: str, validate: bool = True) -> Optional[int]:
    """Newest step whose manifest exists — and, with `validate` (the
    default), whose shards pass checksum validation: a corrupt newest step
    falls back to the previous valid one with a warning rather than
    handing resume a state that explodes mid-np.load."""
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp") and \
                os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
            steps.append(int(d.split("_")[1]))
    for step in sorted(steps, reverse=True):
        if not validate:
            return step
        reason = validate_step(ckpt_dir, step)
        if reason is None:
            return step
        warnings.warn(
            f"checkpoint step {step} in {ckpt_dir} failed validation "
            f"({reason}); falling back to the previous step", stacklevel=2)
    return None


def restore(ckpt_dir: str, step: int, tree_like,
            shardings=None, validate: bool = True) -> Tuple[Any, Dict]:
    """Restore into the structure of `tree_like`, re-sharding if shardings
    (a matching pytree of NamedSharding or None) is given — this is the
    elastic-restart path: the saved mesh need not match the current one.
    With `validate` (default), shard checksums are re-checked first and a
    mismatch raises `CheckpointCorrupt` instead of an opaque npz error."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    if validate:
        reason = validate_step(ckpt_dir, step)
        if reason is not None:
            raise CheckpointCorrupt(
                f"checkpoint step {step} in {ckpt_dir} is corrupt: {reason}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "shard_0.npz"))

    flat_like = _flatten(tree_like)
    shard_flat = _flatten(shardings) if shardings is not None else {}
    keymeta = manifest["keys"]
    out = {}
    for k, like in flat_like.items():
        arr = data[k.replace(_SEP, "__")]
        logical = keymeta.get(k, {}).get("dtype", str(arr.dtype))
        if logical != str(arr.dtype):
            if arr.dtype in (np.uint16, np.uint8) and logical not in (
                    "uint16", "uint8"):
                arr = arr.view(jax.numpy.dtype(logical))  # raw-byte round-trip
            else:
                arr = arr.astype(logical)
        want_dtype = getattr(like, "dtype", arr.dtype)
        v = arr if str(want_dtype) == str(arr.dtype) else \
            np.asarray(jax.numpy.asarray(arr).astype(want_dtype))
        sh = shard_flat.get(k)
        out[k] = jax.device_put(v, sh) if sh is not None else jax.numpy.asarray(v)

    leaves_like, treedef = jax.tree_util.tree_flatten(tree_like)
    keys = list(_flatten(tree_like).keys())
    restored = jax.tree_util.tree_unflatten(treedef, [out[k] for k in keys])
    return restored, manifest.get("extra", {})
