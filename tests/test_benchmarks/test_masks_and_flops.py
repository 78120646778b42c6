"""The mask rule, the area functions and the FLOP arithmetic."""

import numpy as np
import pytest

from benchmarks import flops, masks

QUANTILE = {
    "type": "varlen_block_causal",
    "rule": "quantile",
    "histogram": "data/doc_length_distribution.csv",
    "order_seed": 23,
}


@pytest.mark.parametrize("total", [4096, 16384, 65536, 262144])
def test_quantile_mask_fills_total_and_is_capped(total):
    m = masks.build_mask(QUANTILE, total)
    assert sum(m.doc_lengths) == total
    assert min(m.doc_lengths) >= 1
    assert max(m.doc_lengths) <= total // 4
    assert m.area == sum(n * (n + 1) // 2 for n in m.doc_lengths)
    assert m.cu_seqlens[0] == 0 and m.cu_seqlens[-1] == total


@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 + 11])
def test_no_seed_moves_a_mask(seed):
    """``--seed`` reaches no mask: build_mask takes none, and a process
    whose numpy and python generators were seeded gives the same
    lengths, count and area."""
    import random

    want = masks.build_mask(QUANTILE, 16384, index=3)
    np.random.seed(seed % 2**32)
    random.seed(seed)
    got = masks.build_mask(QUANTILE, 16384, index=3)
    assert got.doc_lengths == want.doc_lengths
    assert got.area == want.area
    assert "seed" not in masks.build_mask.__code__.co_varnames


def test_stream_masks_differ_and_repeat():
    stream = [masks.build_mask(QUANTILE, 16384, index=k) for k in range(6)]
    assert len({m.doc_lengths for m in stream}) == 6
    assert len({len(m.doc_lengths) for m in stream}) > 1  # counts differ
    again = [masks.build_mask(QUANTILE, 16384, index=k) for k in range(6)]
    assert [m.doc_lengths for m in again] == [m.doc_lengths for m in stream]
    assert masks.stream_phi(0) == 0.5
    assert masks.stream_phi(1) == pytest.approx(0.5 + masks.GOLDEN - 1.0)


def test_order_seed_permutes_and_moves_no_length():
    a = masks.build_mask(QUANTILE, 16384)
    b = masks.build_mask({**QUANTILE, "order_seed": 99}, 16384)
    assert sorted(a.doc_lengths) == sorted(b.doc_lengths)
    assert a.doc_lengths != b.doc_lengths
    assert a.area == b.area


def test_quantile_length_follows_the_histogram():
    hist = masks.load_histogram(masks._HERE + "/data/doc_length_distribution.csv")
    lo, hi, cum = hist
    assert cum[-1] == pytest.approx(1.0)
    # 46.75% of documents are at most 500 tokens (the CSV's own column)
    assert masks.quantile_length(0.4674, hist, 10**9) <= 500
    assert masks.quantile_length(0.4676, hist, 10**9) >= 501
    assert masks.quantile_length(0.999999, hist, 4096) == 4096  # the cap
    lengths = [masks.quantile_length(u, hist, 10**9)
               for u in np.linspace(0, 0.9999, 400)]
    assert lengths == sorted(lengths)  # a quantile function is monotone


MASKS_512 = [
    {"type": "causal"},
    {"type": "swa_causal", "window": 64},
    {"type": "swa_causal", "window": 1},
    {"type": "swa_causal", "window": 600},
    {"type": "chunk_causal", "chunk": 100},
    {"type": "chunk_causal", "chunk": 128},
    {"type": "varlen_block_causal", "lengths": [100, 12, 400]},
    QUANTILE,
]


@pytest.mark.parametrize("spec", MASKS_512, ids=lambda s: f"{s['type']}-{s.get('window', s.get('chunk', ''))}")
def test_area_and_allowed_against_brute_force(spec):
    m = masks.build_mask(spec, 512)
    dense = masks.slices_to_dense(m)  # from the slices and type codes
    assert m.area == int(dense.sum())
    pos = np.arange(512)
    assert (masks.allowed(m, pos, pos) == dense).all()  # from the definition
    assert m.causal_share == pytest.approx(dense.sum() / (512 * 513 / 2))


def test_slices_agree_with_the_programs_own_mask():
    """The slice lists mean to the program what they mean here."""
    from magiattention_tpu.common import AttnMaskType, AttnRanges
    from magiattention_tpu.common.mask import make_attn_mask_from_ranges

    for spec in MASKS_512:
        m = masks.build_mask(spec, 512)
        theirs = make_attn_mask_from_ranges(
            AttnRanges.from_ranges(list(m.q_ranges)),
            AttnRanges.from_ranges(list(m.k_ranges)),
            [AttnMaskType(t) for t in m.types], 512, 512,
        )
        assert (np.asarray(theirs) == masks.slices_to_dense(m)).all(), spec


def test_unknown_mask_type_and_bad_lengths_are_errors():
    with pytest.raises(ValueError, match="unknown mask type"):
        masks.build_mask({"type": "nope"}, 512)
    with pytest.raises(ValueError, match="sum to"):
        masks.build_mask({"type": "varlen_block_causal", "lengths": [5]}, 512)


def test_flops_against_brute_force_count():
    m = masks.build_mask(MASKS_512[6], 512)
    pairs = int(masks.slices_to_dense(m).sum())
    hq, d = 4, 32
    # QK^T and PV: a multiply-add per allowed pair, head and channel, twice
    assert flops.attn_fwd_flops(m.area, hq, d) == 2 * 2 * pairs * hq * d
    assert flops.attn_bwd_flops(m.area, hq, d) == 2.5 * flops.attn_fwd_flops(m.area, hq, d)
    assert flops.attn_fwdbwd_flops(m.area, hq, d) == 3.5 * flops.attn_fwd_flops(m.area, hq, d)


def test_decoder_params_match_a_real_pytree():
    import jax

    from benchmarks.kinds.train_stream import _llama_config
    from magiattention_tpu.models import init_params

    cfg = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
           "num_hidden_layers": 3, "num_key_value_heads": 2, "head_dim": 16,
           "rope_theta": 1e4, "vocab_size": 128}
    shapes = jax.eval_shape(
        lambda r: init_params(r, _llama_config(cfg, {"dtype": "float32", "remat": False})),
        jax.random.PRNGKey(0),
    )
    matmul = sum(
        int(np.prod(s.shape)) for path, s in jax.tree_util.tree_leaves_with_path(shapes)
        if len(s.shape) == 2 and "embed" not in str(path)
    )
    assert flops.decoder_matmul_params(cfg) == matmul
    area = 1000
    assert flops.train_step_flops(cfg, 50, area) == (
        6.0 * matmul * 50 + 3 * 3.5 * 4 * area * 4 * 16
    )


def test_peaks_table_is_keyed_by_device_kind():
    assert flops.load_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(KeyError, match="no peaks"):
        flops.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.load_peaks("_source")
    assert flops.roofline_pct(197e12, 2.0, 197.0) == pytest.approx(50.0)
