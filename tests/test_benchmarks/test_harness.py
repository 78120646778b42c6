"""The harness is driven by data: cells, configurations, traffic and
per-layer metrics are found by name, and a toy benchmark made of new
files only (``data/toy``) runs through the same command."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import harness

REPO = harness.CHECKOUT
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "toy")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contracts_form():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    cells = {w["name"] for w in b["workloads"]}
    assert len(cells) == len(b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(len(cells) // 4, 1)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


def test_every_cell_loads_with_its_files():
    b = _bench()
    for w in b["workloads"]:
        cell = harness.load_cell(REPO, w["name"])
        assert cell.chips == w["chips"]
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        assert cell.traffic["kind"] in ("attn_iter", "train_stream")
        for spec in cell.per_layer:  # each source kind is a module
            harness.importlib.import_module(
                f"benchmarks.sources.{spec['source']['kind']}"
            )
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(REPO, "no-such-cell")


def test_configs_state_widths_as_published():
    mistral = harness.load_cell(REPO, "mistral7b-train-16k-onemask").config
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32768, "rope_theta": 1000000.0,
                 "rms_norm_eps": 1e-05, "max_position_embeddings": 32768}
    assert {k: mistral[k] for k in published} == published
    assert set(mistral["reduced"]) == {"num_hidden_layers"}
    attn = harness.load_cell(REPO, "magi64x8-attn-64k-causal").config
    assert (attn["num_attention_heads"], attn["num_key_value_heads"],
            attn["head_dim"], attn["dtype"]) == (64, 8, 128, "bfloat16")


def test_a_toy_benchmark_of_new_files_only_loads():
    """A cell, a configuration, a traffic file (with a mask type no real
    cell uses) and a per-layer metric, each a new file plus one entry."""
    cell = harness.load_cell(TOY, "toy.chunkcausal")
    assert cell.config["head_dim"] == 64
    assert cell.traffic["mask"]["type"] == "chunk_causal"
    assert "toy_fwd_iter_ms" in [m["name"] for m in cell.per_layer]
    real = {m["name"] for m in _bench()["per_layer"]}
    assert "toy_fwd_iter_ms" not in real


def test_metric_file_must_agree_with_benchmark_json(tmp_path):
    import shutil

    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    path = root / "benchmarks" / "metrics" / "toy_fwd_iter_ms.json"
    spec = json.loads(path.read_text())
    spec["unit"] = "s"
    path.write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match="unit"):
        harness.load_cell(str(root), "toy.varlen")


def test_no_accelerator_is_an_error_not_a_fallback(capsys):
    with pytest.raises(SystemExit, match="measures a TPU"):
        harness.main(
            ["--workload", "toy.varlen", "--seconds", "1", "--root", TOY]
        )
    assert "correct" not in capsys.readouterr().out  # no result line


def _rehearse(workload: str, trace: int, seconds: float = 1.0) -> dict:
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(
            ["--workload", workload, "--seed", str(2**31 + 12345),
             "--seconds", str(seconds), "--trace", str(trace), "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def _as_the_command_runs():
    """32-bit, as the command runs on the chip (the suite turns 64-bit
    mode on), and the compile-cache switch left as found."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "workload,trace",
    [("toy.varlen", 0), ("toy.chunkcausal", 1), ("toy.cp4", 1),
     ("toy.train", 1), ("toy.onemask", 0), ("toy.onemask", 1)],
)
def test_rehearsal_prints_the_result_line(workload, trace, _as_the_command_runs):
    """``benchmarks/run.py``'s own path at toy size on the CPU: the last
    line holds the contract's keys and the cell's metrics."""
    # a toy new-mask step takes 2-3 s on an idle host (it compiles), and
    # one must end inside the window while five other workers run tests
    res = _rehearse(workload, trace, seconds=15.0 if workload == "toy.train" else 1.5)
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(res) == (want | {"breakdown"} if trace else want)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == (4 if workload == "toy.cp4" else 1)
    cell = harness.load_cell(TOY, workload)
    if not trace:
        assert set(res["metrics"]) == set(cell.end_to_end)
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        listed = {m["name"] for m in cell.per_layer}
        assert set(res["metrics"]) <= listed
        # on the CPU there is no device trace and no peak: those readers
        # find nothing and the harness leaves their metrics out
        assert "flex_fwd_roofline" not in res["metrics"]
        if workload == "toy.train":
            assert {"new_mask_ms", "new_mask_compile_s",
                    "train_step_steady_ms"} <= set(res["metrics"])
        elif workload == "toy.onemask":
            assert set(res["metrics"]) == {"train_step_steady_ms"}
        else:
            assert res["metrics"]["attn_compiles_in_window"]["value"] == 0
            assert "toy_fwd_iter_ms" in res["metrics"]
        if workload == "toy.cp4":
            assert res["metrics"]["comm_padding_ratio"]["value"] >= 1.0
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
