"""Workload fingerprint: stability, sensitivity, hashing (ISSUE 2)."""

import numpy as np
import pytest

from magiattention_tpu.tuning import make_fingerprint
from magiattention_tpu.tuning.fingerprint import WorkloadFingerprint, _log2_bucket


def _causal(total):
    return [(0, total)], [(0, total)], [1]


def test_fingerprint_is_deterministic():
    """Two independent constructions over the same workload hash equal —
    the disk cache's correctness hinges on this."""
    a = make_fingerprint(*_causal(65536), 8, 8, head_dim=128)
    b = make_fingerprint(*_causal(65536), 8, 8, head_dim=128)
    assert a == b
    assert a.stable_hash() == b.stable_hash()


def test_fingerprint_accepts_numpy_and_lists():
    qr, kr, ts = _causal(4096)
    a = make_fingerprint(qr, kr, ts, 8, 8)
    b = make_fingerprint(
        np.asarray(qr), np.asarray(kr), np.asarray(ts), 8, 8
    )
    assert a.stable_hash() == b.stable_hash()


def test_fingerprint_separates_shapes():
    """Same total, different mask shape -> different fingerprint: a dense
    causal mask must not share a winner with an SWA band."""
    dense = make_fingerprint(*_causal(16384), 8, 8)
    # narrow sliding band: 16 slices of 1024-wide k windows
    qr = [(i * 1024, (i + 1) * 1024) for i in range(16)]
    kr = [(max(i * 1024 - 1024, 0), (i + 1) * 1024) for i in range(16)]
    swa = make_fingerprint(qr, kr, [1] * 16, 8, 8)
    assert dense.stable_hash() != swa.stable_hash()


def test_fingerprint_separates_head_and_dtype_config():
    base = make_fingerprint(*_causal(8192), 8, 8, dtype="bfloat16")
    gqa = make_fingerprint(*_causal(8192), 8, 2, dtype="bfloat16")
    f32 = make_fingerprint(*_causal(8192), 8, 8, dtype="float32")
    assert len({base.stable_hash(), gqa.stable_hash(), f32.stable_hash()}) == 3


def test_fingerprint_separates_kernel_backend(monkeypatch):
    """A jnp/CPU-measured winner must never be served to a pallas/TPU run
    sharing the cache dir: the execution backend is part of the key."""
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "pallas")
    a = make_fingerprint(*_causal(16384), 8, 8)
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    b = make_fingerprint(*_causal(16384), 8, 8)
    assert a.backend.startswith("pallas@") and b.backend.startswith("jnp@")
    assert a.stable_hash() != b.stable_hash()


def test_fingerprint_separates_tpu_generation(monkeypatch):
    """Winners are chip-specific (cost-model peaks AND measure-mode
    timings): a shared cache dir must never serve one generation's winner
    to another."""
    monkeypatch.setenv("MAGI_ATTENTION_TPU_GENERATION", "v5e")
    a = make_fingerprint(*_causal(16384), 8, 8)
    monkeypatch.setenv("MAGI_ATTENTION_TPU_GENERATION", "v5p")
    b = make_fingerprint(*_causal(16384), 8, 8)
    assert a.generation == "v5e" and b.generation == "v5p"
    assert a.stable_hash() != b.stable_hash()


def test_fingerprint_absorbs_token_jitter():
    """A few tokens of varlen drift (within the same tile grid) stays
    inside the log2 buckets, so near-identical workloads share one cache
    entry. Jitter that crosses a tile boundary genuinely changes the
    tiling and correctly re-keys."""
    a = make_fingerprint(*_causal(16384), 8, 8)
    b = make_fingerprint(*_causal(16384 - 64), 8, 8)
    assert a.stable_hash() == b.stable_hash()


def test_fingerprint_records_constraints():
    """Shard-geometry constraints change the feasible candidate set and
    must therefore key separate cache entries."""
    free = make_fingerprint(*_causal(16384), 8, 8)
    shard = make_fingerprint(*_causal(16384), 8, 8, max_block_q=512)
    assert free.stable_hash() != shard.stable_hash()


def test_fingerprint_dict_roundtrip_is_json_stable():
    import json

    fp = make_fingerprint(*_causal(16384), 8, 8)
    d = fp.as_dict()
    assert json.loads(json.dumps(d)) == d
    assert d["entry_est"]  # one row per candidate rung


def test_fingerprint_ignores_degenerate_slices():
    """Sentinel (n, n) empty slices carry no attention and must not
    perturb any statistic — a sentinel-padded range list fingerprints
    identically to its clean equivalent (same filter the cost model
    applies), so it shares the cache entry instead of re-tuning."""
    qr, kr, ts = _causal(16384)
    clean = make_fingerprint(qr, kr, ts, 8, 8)
    padded = make_fingerprint(
        qr + [(16384, 16384), (0, 0)],
        kr + [(16384, 16384), (512, 512)],
        ts + [0, 1],
        8,
        8,
    )
    assert clean == padded
    assert clean.stable_hash() == padded.stable_hash()


def test_fingerprint_memoized_on_repeat_inputs():
    """Repeat plans must not re-pay the per-slice recount: the derivation
    is memoized on a digest of the canonical slice bytes (digest keys only
    — large varlen range arrays must not be pinned by the memo)."""
    from magiattention_tpu.tuning import fingerprint as fp_mod

    qr = [(i * 256, (i + 1) * 256) for i in range(64)]
    kr = [(0, (i + 1) * 256) for i in range(64)]
    ts = [1] * 64
    fp_mod._FP_MEMO.clear()
    a = make_fingerprint(qr, kr, ts, 8, 8)
    assert len(fp_mod._FP_MEMO) == 1
    b = make_fingerprint(qr, kr, ts, 8, 8)
    assert a is b  # memo hit returns the cached object
    assert all(
        isinstance(k[0], bytes) and len(k[0]) == 32 for k in fp_mod._FP_MEMO
    )


def test_log2_bucket_edges():
    assert _log2_bucket(0) == 0
    assert _log2_bucket(-3) == 0
    assert _log2_bucket(1) == 0
    assert _log2_bucket(2) == 8
    assert _log2_bucket(4096) == 96


def test_fingerprint_v3_carries_sparse_rung_axes():
    """ISSUE 15: the fingerprint records the steps extent per rung and
    the sparse-only rung entry estimates — workloads whose row skew (and
    with it the sparse-vs-row-major ranking) differs must not share a
    cached winner even when their aggregate statistics alias."""
    fp = make_fingerprint([(0, 4096)], [(0, 4096)], [1], 8, 8)
    assert fp.version == WorkloadFingerprint.FINGERPRINT_VERSION == 7
    assert fp.step_est and fp.sparse_entry_est
    # one uniform 4k doc vs 4 skewed docs with the same total: the
    # coarse aggregates may bucket together, the steps extent must not
    uniform = make_fingerprint(
        [(0, 1024), (1024, 2048), (2048, 3072), (3072, 4096)],
        [(0, 1024), (1024, 2048), (2048, 3072), (3072, 4096)],
        [1, 1, 1, 1], 8, 8,
    )
    skewed = make_fingerprint(
        [(0, 3328), (3328, 3584), (3584, 3840), (3840, 4096)],
        [(0, 3328), (3328, 3584), (3584, 3840), (3840, 4096)],
        [1, 1, 1, 1], 8, 8,
    )
    assert uniform.step_est != skewed.step_est
    assert uniform.stable_hash() != skewed.stable_hash()


@pytest.mark.parametrize(
    "hq,hk,d,stale,fresh",
    [
        (20, 20, 256, (128, 512, 5), (256, 512, 5)),
        (16, 16, 128, (128, 512, 8), (256, 512, 8)),
    ],
    ids=["glm", "ouro"],
)
def test_an_older_record_for_a_group_one_mask_is_not_served(
    hq, hk, d, stale, fresh, monkeypatch, tmp_path
):
    """A cache directory the parent of ISSUE 35 filled holds (128, 512, hb)
    for the GLM and Ouro cells' mask, chosen when no bytes were priced. It
    is a version-4 fingerprint's record: under its own hash the version-5
    key never opens it, and planted under the new key's name the stored
    fingerprint does not match. Either way the mask is ranked anew, and the
    decision's event says what the bytes term did, on the miss and on the
    hit that follows."""
    import dataclasses
    import json
    import os

    from benchmarks import masks
    from magiattention_tpu import telemetry
    from magiattention_tpu.tuning import (
        TuningRecord, get_tuning_cache, reset_tuning_cache,
        resolve_block_config,
    )

    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(
        root, "benchmarks", "traffic", "train-16k-packed-mla.json"
    )) as f:
        m = masks.build_mask(json.load(f)["mask"], 16384, index=0)
    monkeypatch.setenv("MAGI_ATTENTION_AUTOTUNE_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("MAGI_ATTENTION_AUTOTUNE", raising=False)
    reset_tuning_cache()
    fp = make_fingerprint(
        m.q_ranges, m.k_ranges, m.types, hq, hk, head_dim=d,
        max_block_q=16384, max_block_k=16384, include_sparse=False,
    )
    old_fp = dataclasses.replace(fp, version=4)
    assert old_fp.stable_hash() != fp.stable_hash()
    record = TuningRecord(*stale, "model", 5.99, None, ())
    cache = get_tuning_cache()
    cache._store_disk(old_fp.stable_hash(), old_fp, record)
    cache._store_disk(fp.stable_hash(), old_fp, record)  # planted
    assert cache.get(fp) == (None, "miss")

    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        seen = len(telemetry.get_event_buffer().events())
        for _ in range(2):  # a miss, then the new record from memory
            assert resolve_block_config(
                m.q_ranges, m.k_ranges, m.types, 16384, 16384, 1, hq, hk, d,
                "bfloat16",
            ) == fresh
        got = [
            ev["args"]
            for ev in telemetry.get_event_buffer().events()[seen:]
            if ev["name"] == "autotune_decision"
        ]
    finally:
        telemetry.set_enabled(was)
        reset_tuning_cache()
    assert [a["cache_layer"] for a in got] == ["none", "memory"]
    for args in got:
        assert (args["bound"], args["rejected_bytes"]) == ("mxu", 1)
        assert 0 < args["hbm_seconds"] < args["mxu_seconds"]
    assert got[0]["mxu_seconds"] == got[1]["mxu_seconds"]
