"""Checkpoint manifest/restore semantics + gradient-compression correctness."""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as CKPT
from repro.optim import compress as GC


def test_save_restore_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.bfloat16)}}
    CKPT.save(str(tmp_path), 7, tree, extra={"data_step": 7})
    assert CKPT.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, extra = CKPT.restore(str(tmp_path), 7, like)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16
    assert extra["data_step"] == 7


def _mixed_tree():
    """Seven leaves: device arrays (bfloat16 among them) and host arrays."""
    return {"x": jnp.arange(128, dtype=jnp.uint32).reshape(1, 64, 2),
            "banks": [jnp.full((1, 2, 64), 7, jnp.uint32),
                      jnp.arange(64, dtype=jnp.uint32).reshape(1, 2, 32)],
            "gen": jnp.asarray([3], jnp.int32),
            "w": jnp.linspace(-2, 2, 12, dtype=jnp.bfloat16).reshape(3, 4),
            "host_w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "host_b": np.asarray([1.5, -0.25], dtype=jnp.bfloat16)}


def test_save_reads_device_once_and_checksums_the_file(tmp_path,
                                                       monkeypatch):
    """One `jax.device_get` for the whole tree; the manifest's CRC and byte
    count are those of the file on disk; the step validates and restores
    bit for bit, bfloat16 leaves included."""
    tree = _mixed_tree()
    assert len(jax.tree.leaves(tree)) >= 5
    calls = []
    real_get = jax.device_get

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    path = CKPT.save(str(tmp_path), 4, tree, extra={"k": 1})
    monkeypatch.undo()
    assert len(calls) == 1

    with open(os.path.join(path, "manifest.json")) as f:
        shard = json.load(f)["shards"]["shard_0.npz"]
    shard_path = os.path.join(path, "shard_0.npz")
    with open(shard_path, "rb") as f:
        on_disk = f.read()
    assert shard["crc32"] == zlib.crc32(on_disk)
    assert shard["bytes"] == len(on_disk) == os.path.getsize(shard_path)
    assert CKPT.validate_step(str(tmp_path), 4) is None

    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)
    restored, extra = CKPT.restore(str(tmp_path), 4, like)
    assert extra == {"k": 1}
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8))


def test_checkpoint_in_the_older_layout_validates_and_restores(tmp_path):
    """The on-disk format, pinned: an npz written to a file by `np.savez`
    (members named by key path with '__', ml_dtypes stored as raw bytes),
    then a manifest with that file's CRC32 and size — as checkpoints were
    written when the CRC was read back from the file."""
    tree = _mixed_tree()
    step_dir = tmp_path / "step_00000012"
    step_dir.mkdir()
    arrays, keys = {}, {}
    for k, leaf in CKPT._flatten(tree).items():
        arr = np.asarray(leaf)
        keys[k] = {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        if arr.dtype == jnp.bfloat16:
            arr = arr.view(np.uint16)
        arrays[k.replace("/", "__")] = arr
    shard_path = step_dir / "shard_0.npz"
    np.savez(str(shard_path), **arrays)
    data = shard_path.read_bytes()
    manifest = {"step": 12, "keys": keys, "extra": {"gens_done": 12},
                "n_hosts": 1, "time": 0.0,
                "shards": {"shard_0.npz": {"crc32": zlib.crc32(data),
                                           "bytes": len(data)}}}
    (step_dir / "manifest.json").write_text(json.dumps(manifest))

    assert CKPT.validate_step(str(tmp_path), 12) is None
    assert CKPT.latest_step(str(tmp_path)) == 12
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)
    restored, extra = CKPT.restore(str(tmp_path), 12, like)
    assert extra == {"gens_done": 12}
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8))


def test_partial_write_is_invisible(tmp_path):
    """Crash mid-save must not leave a checkpoint latest_step would trust."""
    d = tmp_path / "step_00000009.tmp"
    d.mkdir(parents=True)
    (d / "shard_0.npz").write_bytes(b"garbage")
    assert CKPT.latest_step(str(tmp_path)) is None


def test_async_checkpointer_overlap(tmp_path):
    tree = {"w": jnp.ones((256, 256))}
    ck = CKPT.AsyncCheckpointer()
    ck.save(str(tmp_path), 1, tree)
    ck.wait()
    assert CKPT.latest_step(str(tmp_path)) == 1


def test_elastic_restore_to_other_sharding(tmp_path):
    """A checkpoint written on one topology restores onto another (here:
    unsharded -> explicit single-device sharding) — the elastic path."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    CKPT.save(str(tmp_path), 3, tree)
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    sh = {"w": jax.sharding.SingleDeviceSharding(jax.devices()[0])}
    restored, _ = CKPT.restore(str(tmp_path), 3, like, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


# ---------------------------------------------------------------------------


def test_int8_quantization_bounded_error():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = GC.quantize_int8(g)
    deq = GC.dequantize_int8(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) * 0.5 + 1e-7


def test_error_feedback_unbiased_over_time():
    """EF accumulates what quantization drops: summed compressed updates
    converge to summed true gradients."""
    rng = np.random.default_rng(1)
    true_sum = np.zeros(64, np.float32)
    sent_sum = np.zeros(64, np.float32)
    r = jnp.zeros(64, jnp.float32)
    for i in range(200):
        g = jnp.asarray(rng.normal(size=64).astype(np.float32))
        true_sum += np.asarray(g)
        gq = g + r
        q, s = GC.quantize_int8(gq)
        deq = GC.dequantize_int8(q, s)
        r = gq - deq
        sent_sum += np.asarray(deq)
    resid = np.abs(true_sum - sent_sum)
    assert resid.max() <= float(jnp.max(jnp.abs(r))) + 1e-5
