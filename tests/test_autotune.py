"""Kernel autotune DB tests (reference: phi/kernels/autotune/cache.h —
AutoTuneCache keyed lookup; CINN auto_schedule/database persistence)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.autotune import (TuneDB, flash_attention_config,
                                            get_db)


def test_bucket_powers_of_two():
    assert TuneDB.bucket(1) == 128
    assert TuneDB.bucket(128) == 128
    assert TuneDB.bucket(129) == 256
    assert TuneDB.bucket(2048) == 2048
    assert TuneDB.bucket(3000) == 4096


def test_key_buckets_seq_dims_only():
    k1 = TuneDB.key("fa", "TPU v5e", "bfloat16", sq=2000, sk=2048, d=128)
    k2 = TuneDB.key("fa", "TPU v5e", "bfloat16", sq=2048, sk=2048, d=128)
    assert k1 == k2
    k3 = TuneDB.key("fa", "TPU v5e", "bfloat16", sq=2048, sk=2048, d=64)
    assert k3 != k1  # d is not a seq dim: kept exact


def test_record_save_load_roundtrip(tmp_path):
    path = str(tmp_path / "db.json")
    db = TuneDB(path)
    key = TuneDB.key("flash_attention", "TPU v5e", "bfloat16",
                     sq=2048, sk=2048, d=128, causal=1)
    db.record(key, {"block_q": 256, "block_k": 512, "us": 123.4})
    db.save()
    fresh = TuneDB(path)
    hit = fresh.lookup(key)
    assert hit == {"block_q": 256, "block_k": 512, "us": 123.4}
    # merge-over: a second save with a different key keeps the first
    db2 = TuneDB(path)
    db2.record("other|key", {"block_q": 128, "block_k": 128})
    db2.save()
    data = json.load(open(path))
    assert key in data and "other|key" in data


def test_corrupt_db_warns_with_path(tmp_path):
    """Satellite (ISSUE 2): a corrupt DB file must not silently load
    nothing — offline-tuned configs vanishing without a trace. One warning
    naming the path."""
    import warnings

    path = str(tmp_path / "corrupt.json")
    with open(path, "w") as f:
        f.write("{not valid json")
    db = TuneDB(path)
    with pytest.warns(RuntimeWarning, match="corrupt kernel tune DB"):
        db.lookup("whatever|key")
    # a MISSING DB stays silent (the common no-sweep-yet case)
    fresh = TuneDB(str(tmp_path / "absent.json"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fresh.lookup("whatever|key")


def test_dispatch_uses_db_on_tpu(monkeypatch, tmp_path):
    """flash_attention_config consults the DB when the backend is TPU."""
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops import registry

    path = str(tmp_path / "db.json")
    key = TuneDB.key("flash_attention", "TPU v5e", "bfloat16",
                     sq=4096, sk=4096, d=128, causal=1)
    json.dump({key: {"block_q": 512, "block_k": 256}}, open(path, "w"))

    fresh = TuneDB(path)
    monkeypatch.setattr(autotune, "_DB", fresh)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")

    class FakeDev:
        device_kind = "TPU v5e"

    import jax
    monkeypatch.setattr(autotune, "flash_attention_config",
                        autotune.flash_attention_config)
    real_devices = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()])
    try:
        bq, bk = flash_attention_config(4096, 4096, 128, "bfloat16", True)
    finally:
        monkeypatch.setattr(jax, "devices", real_devices)
    assert (bq, bk) == (512, 256)
    # unknown shape falls back to defaults
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()])
    # unknown shape: shape-aware heuristic defaults (largest dividing
    # candidate — the round-3 hardware sweep favors big blocks)
    bq, bk = flash_attention_config(1024, 1024, 64, "bfloat16", False)
    assert (bq, bk) == (512, 1024)
    bq, bk = flash_attention_config(384, 384, 64, "bfloat16", False)
    assert (bq, bk) == (128, 128)


def test_dispatch_defaults_on_cpu():
    assert flash_attention_config(256, 256, 64, "float32", True) \
        == (128, 128)


def test_flash_attention_auto_blocks_still_correct():
    """End-to-end: block sizes resolved via autotune path (defaults on CPU)
    produce the same result as explicit blocks."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (1, 128, 2, 32)), jnp.float32)
    auto = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    manual = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                    block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(manual),
                               rtol=2e-5, atol=2e-5)


def test_shipped_db_nonempty_and_consulted(monkeypatch):
    """Round-3 invariant: the in-repo tune DB carries real-hardware
    winners (the round-2 DB shipped empty) and dispatch returns them for
    the bench shape on the recorded device kind."""
    import json as _json
    import os
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops import registry

    shipped = _json.load(open(autotune._SHIPPED))
    assert shipped, "shipped tune_db.json is empty"
    key = TuneDB.key("flash_attention", "TPU v5 lite", "bfloat16",
                     sq=2048, sk=2048, d=128, causal=1)
    assert key in shipped, f"bench-shape key missing: {key}"

    fresh = TuneDB()
    monkeypatch.setattr(autotune, "_DB", fresh)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")

    class FakeDev:
        device_kind = "TPU v5 lite"

    import jax
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()])
    try:
        bq, bk = flash_attention_config(2048, 2048, 128, "bfloat16", True)
    finally:
        monkeypatch.setattr(jax, "devices", real)
    rec = shipped[key]
    assert (bq, bk) == (rec["block_q"], rec["block_k"])
