"""The metric files that read the part scopes (ISSUE 34), on a hand-made
trace in the names the v5e gives: ``fusion.N <scope>``, containers
(``while.N``, ``conditional.N``) beside their bodies' operations, jax's
``transpose(jvp())`` and ``checkpoint/rematted_computation`` round the
program's scopes, the compiler's copies with no scope at all. Each new
metric counts its own operations and no other part's, the parts and the
remainder add up to busy time, and every metric the benchmark had reads
what it read before the new scopes were nested in."""

import dataclasses
import importlib
import json
import os
import re

import pytest

from benchmarks import harness, trace_reduce as tr
from benchmarks.trace_reduce import Op, Span, Trace

METRICS = os.path.join(harness.CODE_DIR, "metrics")
NEW_SCOPES = re.compile(
    r"\b(magi_layout|magi_bwd_delta|magi_embed|magi_proj|magi_ffn|magi_head"
    r"|magi_optimizer|magi_moe_(sort|gather|matmul|scatter))\b/?"
)

J, T, R = "jit(step)/jvp()/", "jit(step)/transpose(jvp())/", "rematted_computation/"
FULL = "magi_attn_full/magi_merged_kernel/"
SLIDE = "magi_attn_sliding/magi_merged_kernel/"
EXP = "magi_moe_experts/"
# (instruction name, scope, ns, the new metrics that count it)
STEP_OPS = [
    ("fusion.1", J + "magi_embed/gather", 10, "embed"),
    ("fusion.2", T + "magi_embed/scatter-add", 10, "embed"),
    ("fusion.3", J + "magi_mtp/magi_embed/dot_general", 5, "embed"),
    ("fusion.10", J + "checkpoint/magi_proj/dot_general", 40, "proj"),
    ("fusion.11", T + "checkpoint/" + R + "magi_proj/dot_general", 40, "proj remat"),
    ("fusion.12", T + "checkpoint/magi_proj/magi_mla_q/dot_general", 20, "proj"),
    ("fusion.13", J + "vmap(magi_proj)/dot_general", 7, "proj"),
    ("fusion.14", T + "checkpoint/magi_mtp/magi_proj/magi_mla_out/dot_general", 3, "proj"),
    ("copy_bitcast_fusion.1", J + "checkpoint/magi_attn_full/magi_layout/transpose", 6, "layout"),
    ("pad_bitcast_fusion", T + "checkpoint/" + R + FULL + "magi_layout/transpose", 2, "layout remat"),
    ("multiply_reduce_fusion", T + "checkpoint/" + FULL + "magi_bwd_delta/reduce_sum", 4, "layout"),
    ("while.20", J + "checkpoint/" + FULL + "magi_layout/jit(searchsorted)/vmap()/while", 1, ""),
    ("fusion.15", J + "checkpoint/" + FULL + "magi_layout/jit(searchsorted)/vmap()/while/body/closed_call/gather", 1, "layout"),
    ("magi_flex_fwd_kernel.1", J + "checkpoint/" + FULL + "magi_flex_fwd_kernel/pallas_call", 30, "flex"),
    ("magi_flex_fwd_kernel.2", T + "checkpoint/" + R + SLIDE + "magi_flex_fwd_kernel/pallas_call", 30, "flex remat"),
    ("magi_flex_dq_kernel.3", T + "checkpoint/" + SLIDE + "magi_flex_dq_kernel/pallas_call", 35, "flex"),
    ("magi_flex_dkv_kernel.4", T + "checkpoint/magi_mtp/" + FULL + "magi_flex_dkv_kernel/pallas_call", 45, "flex"),
    ("fusion.20", J + "checkpoint/magi_ffn/dot_general", 50, "ffn"),
    ("fusion.21", T + "checkpoint/" + R + "magi_ffn/dot_general", 50, "ffn remat"),
    ("fusion.30", J + "checkpoint/magi_moe_router/dot_general", 5, "moe"),
    ("sort.3", J + "checkpoint/" + EXP + "magi_moe_sort/sort", 27, "moe sort"),
    ("while.7", T + "checkpoint/" + EXP + "while", 73, ""),
    ("conditional.2", T + "checkpoint/" + EXP + "while/body/cond", 73, ""),
    ("fusion.31", T + "checkpoint/" + EXP + "while/body/cond/branch_1_fun/checkpoint/magi_moe_gather/gather", 12, "moe gather"),
    ("ragged-dot.5", "", 10, "moe matmul"),
    ("fusion.32", T + "checkpoint/" + EXP + "while/body/cond/branch_1_fun/checkpoint/" + R + "magi_moe_matmul/jit(_where)/select_n", 4, "moe matmul remat"),
    ("fusion.33", T + "checkpoint/" + EXP + "while/body/cond/branch_1_fun/checkpoint/magi_moe_scatter/scatter-add", 47, "moe scatter"),
    ("fusion.34", J + "checkpoint/magi_moe_shared/dot_general", 8, "moe"),
    ("fusion.74", T + "vmap(magi_head)/dot_general", 60, "head"),
    ("fusion.75", T + "magi_mtp/magi_head/dot_general", 10, "head"),
    ("while.1195", J + "magi_exit_head/while", 15, ""),
    ("fusion.80", J + "magi_exit_head/while/body/closed_call/checkpoint/dot_general", 15, "exit_head"),
    ("while.1196", J + "magi_loop/while", 4, ""),
    ("fusion.81", J + "magi_loop/while/body/magi_head/reduce_sum", 4, "head"),
    ("fusion.90", "jit(step)/magi_optimizer/mul", 12, "optimizer"),
    ("copy.7", "", 3, "unscoped"),
    ("copy.8", "k", 1, "unscoped"),
    ("fusion.96", "", 2, "unscoped"),
    ("copy-start.4", "", 1, "unscoped"),
    ("all-reduce.1", T + "psum", 1, "unscoped"),
    ("fusion.97", J + "magi_mtp/slice", 2, "unscoped"),  # a cross-cut hides nothing
]
STEP_METRICS = {
    "train_embed_share": "embed", "train_proj_share": "proj",
    "train_attn_layout_share": "layout", "train_ffn_share": "ffn",
    "train_head_share": "head", "train_optimizer_share": "optimizer",
    "train_moe_sort_share": "sort", "train_moe_gather_share": "gather",
    "train_moe_matmul_share": "matmul", "train_moe_scatter_share": "scatter",
    "train_remat_share": "remat", "train_unscoped_share": "unscoped",
}

A, B = "jit(fwdbwd)/jvp()/shard_map/", "jit(fwdbwd)/transpose(jvp())/shard_map/"
CAST = "magi_merged_cast/magi_group_cast/"
# (device, name, scope, ns, metrics): two ranks, so a cast exists
ATTN_OPS = {
    "fwd": [
        (0, "copy_bitcast_fusion", "jit(fwd)/shard_map/magi_layout/transpose", 8, "layout"),
        (0, "magi_flex_fwd_kernel.1", "jit(fwd)/shard_map/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call", 60, ""),
        (0, "slice_reduce_fusion", "jit(fwd)/shard_map/magi_layout/slice", 4, "layout"),
        (0, "copy.7", "", 3, ""),
        (1, "copy_bitcast_fusion", "jit(fwd)/shard_map/magi_layout/transpose", 10, "layout"),
        (1, "magi_flex_fwd_kernel.1", "jit(fwd)/shard_map/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call", 62, ""),
    ],
    "fwdbwd": [
        (0, "collective-permute-start.1", A + CAST + "ppermute", 2, "cast"),
        (0, "fusion.5", A + CAST + "jit(_take)/gather", 5, "cast"),
        (0, "concatenate.1", A + "magi_layout/concatenate", 3, "layout"),
        (0, "pad_bitcast_fusion", A + "magi_merged_kernel/magi_layout/transpose", 2, "layout"),
        (0, "magi_flex_fwd_kernel.3", A + "magi_merged_kernel/magi_flex_fwd_kernel/pallas_call", 60, ""),
        (0, "multiply_reduce_fusion", B + "magi_merged_kernel/magi_bwd_delta/reduce_sum", 6, "layout"),
        (0, "magi_flex_dq_kernel.4", B + "magi_merged_kernel/magi_flex_dq_kernel/pallas_call", 75, ""),
        (0, "magi_flex_dkv_kernel.5", B + "magi_merged_kernel/magi_flex_dkv_kernel/pallas_call", 94, ""),
        (0, "convert_bitcast_fusion", B + "magi_layout/transpose", 9, "layout"),
        (0, "fusion.6", B + CAST + "jit(_take)/scatter-add", 7, "cast"),
        (0, "fusion.7", B + "magi_stage0_lse_merge/exp", 1, "cast"),
        (0, "all-to-all.2", B + "magi_group_reduce_a2a/all_to_all", 4, "cast"),
        (0, "while.46", B + "magi_merged_kernel/magi_layout/jit(searchsorted)/vmap()/while", 1, ""),
        (0, "copy.11", "", 3, "unscoped"),
        (0, "copy.8", "k", 1, "unscoped"),
        (1, "magi_flex_dq_kernel.4", B + "magi_merged_kernel/magi_flex_dq_kernel/pallas_call", 70, ""),
        (1, "convert_bitcast_fusion", B + "magi_layout/transpose", 11, "layout"),
        (1, "fusion.6", B + CAST + "jit(_take)/scatter-add", 9, "cast"),
        (1, "copy.11", "", 5, "unscoped"),
    ],
}
ATTN_METRICS = {
    "attn_layout_fwd_ms": ("fwd", "layout"),
    "attn_layout_fwdbwd_ms": ("fwdbwd", "layout"),
    "attn_cast_fwdbwd_ms": ("fwdbwd", "cast"),
    "attn_unscoped_fwdbwd_ms": ("fwdbwd", "unscoped"),
}
ITERS = {"window": 5, "fwd": 3, "fwdbwd": 2}


def _spec(metric: str) -> dict:
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return json.load(f)


def _back_to_back(rows) -> tuple[list[Op], int]:
    """Leaf operations one after another a device; a container lies
    over the operations that follow it, as a trace lists a ``while``
    beside its body's operations."""
    ops, clock = [], {}
    for dev, name, scope, ns, _tags in rows:
        start = clock.get(dev, 0)
        ops.append(Op(dev, name, start, ns, scope))
        if not re.match(r"while|cond", name):
            clock[dev] = start + ns
    return ops, max(clock.values())


def _step_trace() -> Trace:
    ops, end = _back_to_back([(0, *row) for row in STEP_OPS])
    return Trace(ops, [Span("phase:window", 0, end + 10)])


def _attn_trace() -> Trace:
    fwd, fwd_end = _back_to_back(ATTN_OPS["fwd"])
    bwd, bwd_end = _back_to_back(ATTN_OPS["fwdbwd"])
    t0 = fwd_end + 5
    bwd = [dataclasses.replace(o, start_ns=o.start_ns + t0) for o in bwd]
    return Trace(
        fwd + bwd,
        [Span("phase:window", 0, t0 + bwd_end), Span("phase:fwd", 0, fwd_end),
         Span("phase:fwdbwd", t0, bwd_end)],
    )


def _read(metric: str, trace: Trace):
    """The metric's value as the harness computes it for a result line."""
    spec = _spec(metric)["source"]
    obs = harness.Observations(
        end_to_end={}, attempted=1, failed=0, correct=True, iters=ITERS,
        flops={"attn_fwd": 1e6, "attn_bwd": 2.5e6, "attn_full_executed": 1e6,
               "attn_sliding_executed": 1e6},
    )
    obs.trace, obs.peaks = trace, {"bf16_tflops": 197.0}
    source = importlib.import_module(f"benchmarks.sources.{spec['kind']}")
    return source.read(spec, obs)


def _tagged(rows, tag: str) -> int:
    return sum(r[-2] for r in rows if tag in r[-1].split())


@pytest.mark.parametrize("metric", sorted(STEP_METRICS))
def test_a_step_metric_counts_its_own_operations_and_no_other_parts(metric):
    trace = _step_trace()
    busy_ns = sum(
        ns for name, _s, ns, _t in STEP_OPS if not re.match(r"while|cond", name)
    )
    assert tr.busy_seconds(trace, *trace.phase("window")) == pytest.approx(
        busy_ns / 1e9
    )
    want_ns = _tagged(STEP_OPS, STEP_METRICS[metric])
    assert want_ns > 0
    assert _read(metric, trace) == pytest.approx(100.0 * want_ns / busy_ns)


@pytest.mark.parametrize("metric", sorted(ATTN_METRICS))
def test_an_attention_metric_is_ms_a_call_averaged_over_devices(metric):
    phase, tag = ATTN_METRICS[metric]
    rows = ATTN_OPS[phase]
    per_dev = [_tagged([r for r in rows if r[0] == d], tag) for d in (0, 1)]
    ran = [ns for ns in per_dev if ns]  # devices that ran such an operation
    want_ms = 1e3 * (sum(ran) / len(ran) / 1e9) / ITERS[phase]
    assert _read(metric, _attn_trace()) == pytest.approx(want_ms)


def test_the_cast_metric_reads_nothing_on_one_chip():
    one = Trace(
        [o for o in _attn_trace().ops if "cast" not in o.scope
         and "group_reduce" not in o.scope and "lse_merge" not in o.scope],
        _attn_trace().spans,
    )
    assert _read("attn_cast_fwdbwd_ms", one) is None  # left out of the line
    assert _read("attn_layout_fwdbwd_ms", one) is not None


def test_the_parts_and_the_remainder_add_up_to_busy_time():
    trace = _step_trace()
    parts = [
        "train_embed_share", "train_proj_share", "train_attn_layout_share",
        "train_flex_kernel_share", "train_ffn_share", "train_moe_share",
        "train_head_share", "train_exit_head_share", "train_optimizer_share",
        "train_unscoped_share",
    ]
    assert sum(_read(m, trace) for m in parts) == pytest.approx(100.0)
    # the attention call: kernels, layout, casts and the remainder, a call
    attn = _attn_trace()
    t0, t1 = attn.phase("fwdbwd")
    leaf_ns = {
        d: sum(r[3] for r in ATTN_OPS["fwdbwd"]
               if r[0] == d and not r[1].startswith("while"))
        for d in (0, 1)
    }
    assert tr.busy_seconds(attn, t0, t1) == pytest.approx(
        sum(leaf_ns.values()) / 2 / 1e9
    )
    # device 0 alone runs every kind of operation: there the sum is exact
    dev0 = Trace([o for o in attn.ops if o.device == 0], attn.spans)
    # the forward kernel's file reads the forward phase: its time in the
    # forward+backward call is read by name
    total_ms = sum(
        _read(m, dev0) for m in (
            "flex_dq_kernel_ms", "flex_dkv_kernel_ms", "attn_layout_fwdbwd_ms",
            "attn_cast_fwdbwd_ms", "attn_unscoped_fwdbwd_ms",
        )
    ) + 1e3 * tr.kernel_seconds(dev0, "magi_flex_fwd_kernel", t0, t1) / 2
    assert total_ms == pytest.approx(1e3 * leaf_ns[0] / 1e9 / 2)


def test_the_expert_parts_add_up_to_what_train_moe_share_reads():
    trace = _step_trace()
    inside = sum(
        _read(f"train_moe_{part}_share", trace)
        for part in ("sort", "gather", "matmul", "scatter")
    )
    t0, t1 = trace.phase("window")
    busy = tr.busy_seconds(trace, t0, t1)
    beside = 100.0 * sum(
        tr.kernel_seconds(trace, rf"^(?!while|cond)\S+ \S*{scope}\b", t0, t1)
        for scope in ("magi_moe_router", "magi_moe_shared")
    ) / busy
    assert inside + beside == pytest.approx(_read("train_moe_share", trace))
    assert _read("train_moe_share", trace) == pytest.approx(
        100.0 * _tagged(STEP_OPS, "moe") / (busy * 1e9)
    )
    # sort : gather : matmul : scatter as the rows give them
    assert [
        round(_read(f"train_moe_{p}_share", trace) * busy * 1e7)
        for p in ("sort", "gather", "matmul", "scatter")
    ] == [27, 12, 14, 47]


HAD = sorted(
    f[:-5] for f in os.listdir(METRICS)
    if f.endswith(".json") and f[:-5] not in {**STEP_METRICS, **ATTN_METRICS}
    and _spec(f[:-5])["source"]["kind"].startswith("trace_kernel")
)


@pytest.mark.parametrize("metric", HAD)
def test_a_metric_the_benchmark_had_reads_what_it_read(metric):
    """The same trace with the new scopes' names taken out of every scope
    is the trace the parent's program gives (the new scopes nest inside
    or outside the old ones and rename nothing): each older metric file
    reads the same value on both."""

    def as_before(trace: Trace) -> Trace:
        return Trace(
            [dataclasses.replace(o, scope=NEW_SCOPES.sub("", o.scope))
             for o in trace.ops],
            trace.spans,
        )

    assert len(HAD) == 14
    trace = _attn_trace() if _spec(metric)["moves"].startswith("attn") else (
        _step_trace()
    )
    before = as_before(trace)
    assert before.ops != trace.ops
    assert not [o for o in before.ops if NEW_SCOPES.search(o.scope)]
    now, then = _read(metric, trace), _read(metric, before)
    assert now is not None and now == pytest.approx(then, rel=1e-12)
