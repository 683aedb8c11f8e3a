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
    # unknown shape: the rule's blocks (the widest, clipped to the length;
    # they need not divide it, and a length of several is cut evenly)
    bq, bk = flash_attention_config(1024, 1024, 64, "bfloat16", False)
    assert (bq, bk) == (1024, 1024)
    bq, bk = flash_attention_config(384, 384, 64, "bfloat16", False)
    assert (bq, bk) == (384, 384)
    assert flash_attention_config(2688, 2688, 64, "bfloat16", True) == (1408,
                                                                       1408)


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
    readings (the round-2 DB shipped empty). Since PR 42 it holds no
    flash-attention entry: the sweep on the chip read the rule's own blocks
    fastest at every shape, and a table beside a rule that says the same is
    a second place to be wrong. Dispatch on the recorded device kind gives
    the rule's, and an entry a later sweep records still wins
    (``test_dispatch_uses_db_on_tpu``)."""
    import json as _json
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops import registry

    shipped = _json.load(open(autotune._SHIPPED))
    assert shipped, "shipped tune_db.json is empty"
    assert not [k for k in shipped if k.startswith("flash_attention|")]

    monkeypatch.setattr(autotune, "_DB", TuneDB())
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    monkeypatch.setattr(autotune, "_device_kind",
                        lambda default="cpu": "TPU v5 lite")
    for s, blocks in ((2048, (2048, 2048)), (4096, (2048, 2048)),
                      (1920, (1920, 1920)), (2688, (1408, 1408))):
        assert flash_attention_config(s, s, 128, "bfloat16", True) == blocks
        assert autotune._default_blocks(s, s, 128) == blocks


def test_the_flash_sweep_prints_what_it_was_asked_to_make(capsys,
                                                         monkeypatch):
    """``tools/tune_kernels.py --flash`` in interpret mode: every timed
    candidate's line carries the plan the kernel ran (blocks as clipped,
    the classes' counts, the edge blocks' parts, the backward's form)
    beside its time; the rule's own choice runs; and a candidate that does
    not divide the length (320 in blocks of 128) is timed, not skipped."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    import tune_kernels
    monkeypatch.setattr(sys, "argv",
                        ["tune_kernels.py", "--flash", "--interpret"])
    tune_kernels.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    sweep = [l for l in lines
             if l.get("bench") in ("flash_attention_fwd",
                                   "flash_attention_fwdbwd")]
    assert {l["bench"] for l in sweep} == {"flash_attention_fwd",
                                           "flash_attention_fwdbwd"}
    plan = {"block_q", "block_k", "nq", "nk", "interior", "edge", "dead",
            "fwd_parts", "bwd_parts", "backward", "group"}
    assert all(plan <= set(l) and l["pallas_us"] > 0 for l in sweep)
    assert sum(l["rule"] for l in sweep) == 2
    ragged = [l for l in sweep if l["nq"] * l["block_q"] > 320]
    assert ragged and all(l["block_q"] == 128 for l in ragged)
    assert lines[-1] == {"tuned": False, "cases": len(sweep)}
