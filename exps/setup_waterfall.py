"""Where a benchmark cell's set-up went, from the program's own span ring.

    python3 exps/setup_waterfall.py run --workload <cell> --seed <n> \\
        --seconds 38 --out <dir>
    python3 exps/setup_waterfall.py show <dir>/<cell>.<seed>.json ...

``run`` is ``benchmarks/run.py --trace 1`` (the same ``harness.main``, the
same log, the same last line) that also keeps what the harness throws
away when it exits: the ring, written by ``telemetry.dump_events`` to
``<cell>.<seed>.events.json``, and beside it ``<cell>.<seed>.json`` with
the clock read set-up is counted from, ``setup_s`` as the harness logged
it, the result line's metrics, and the window's device time by scope from
the trace the harness left in ``.bench_out/``. Run it twice on one
checkout: while telemetry is on the persistent cache keys on metadata, so
the first run compiles everything and the second loads it, which is the
path an untraced run's ``setup_s`` is judged on
(``docs/observability.md``, "Reading a set-up").

``show`` prints the waterfall (the stretches of set-up some span covers,
as ``setup_unspanned_s`` counts them, by the root span each lies under;
the remainder and its largest gaps) and each program's trace by part:
``trace_part`` self-seconds by ``scope`` beside the same scope's share of
the device's time in the window, and where in the trace its unscoped time
lies.
"""

import collections
import contextlib
import io
import json
import os
import re
import sys
import time

_T_START = time.perf_counter()  # set-up counts from here, as in run.py

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# the phases in which Python of the program runs (a kernel's body is
# traced under its lowering)
TRACED = ("jax.trace", "jax.lower")


# ---------------------------------------------------------------------------
# show: pure functions over a ring
# ---------------------------------------------------------------------------


def _label(ev: dict) -> str:
    fun = ev["args"].get("fun_name")
    return f"{ev['name']} {fun}" if fun else ev["name"]


def waterfall(events, began_s: float, opened_s: float):
    """(rows, gaps, unspanned seconds): the stretches of
    ``[began_s, opened_s]`` (perf_counter seconds) that a span covers
    (``program_span_uncovered.cover``: the metric's own union), summed
    by the label of the root span each lies under, so that rows +
    unspanned = ``opened_s - began_s`` and unspanned is what
    ``setup_unspanned_s`` reads. A cache load is told apart from the
    compile phase it ended. ``gaps`` are the stretches nothing covers:
    (seconds, the root before, the root after)."""
    from benchmarks.sources.program_span_uncovered import cover

    by_id = {ev["args"]["id"]: ev for ev in events}
    loads = collections.Counter()  # seconds of cache load by parent's id
    for ev in events:
        if ev["name"] == "jax.cache_load" and ev["args"].get("parent") in by_id:
            loads[ev["args"]["parent"]] += ev["dur"] / 1e6
    rows: dict[str, list[float]] = {}
    gaps, edge, before = [], began_s, "set-up begins"
    for a, b, ev in cover(events, 1e6 * began_s, 1e6 * opened_s):
        a, b, top = a / 1e6, b / 1e6, ev
        while top["args"].get("parent") in by_id:
            top = by_id[top["args"]["parent"]]
        label = _label(top)
        if a > edge:
            gaps.append((a - edge, before, label))
        seconds = b - a
        loaded = min(loads.pop(ev["args"]["id"], 0.0), seconds)
        for name, part in (
            (label.replace("backend_compile", "cache_load"), loaded),
            (label, seconds - loaded),
        ):
            if part:
                row = rows.setdefault(name, [0.0, 0])
                row[0] += part
                row[1] += 1
        edge, before = b, label
    if opened_s > edge:
        gaps.append((opened_s - edge, before, "the window opens"))
    unspanned = (opened_s - began_s) - sum(r[0] for r in rows.values())
    return rows, sorted(gaps, reverse=True), unspanned


def trace_by_scope(events, opened_s: float):
    """{program: {scope: [self seconds, spans]}} for every trace or
    lowering that ended before the window: ``trace_part`` self time by
    ``scope``, every other nested span by its name, and the phase's own
    self time as ``(unscoped)``: jax's work, and Python under no scope."""
    from magiattention_tpu.telemetry import span_self_seconds

    own = span_self_seconds(events)
    by_id = {ev["args"]["id"]: ev for ev in events}
    out: dict[str, dict[str, list]] = {}
    for ev in events:
        if (ev["ts"] + ev["dur"]) / 1e6 > opened_s:
            continue
        top = ev
        while top["name"] not in TRACED:
            top = by_id.get(top["args"].get("parent"))
            if top is None:
                break
        if top is None:
            continue
        scope = "(unscoped)" if ev is top else ev["args"].get("scope", ev["name"])
        row = out.setdefault(_label(top), {}).setdefault(scope, [0.0, 0])
        row[0] += own[ev["args"]["id"]]
        row[1] += 1
    return out


def unscoped_by_place(events, opened_s: float):
    """{program: [before the first part, between parts, after the last]}:
    where in a trace or lowering that holds a part its ``(unscoped)``
    seconds lie. After the last part's end the program's Python under a
    scope has returned (what is left is the caller's code under no scope
    and jax's own: the jaxpr's closing); between parts lie Python of the
    program under no scope and what jax does between two scopes (a
    checkpointed layer's differentiation, the transposition)."""
    children = collections.defaultdict(list)
    for ev in events:
        children[ev["args"].get("parent")].append(ev)
    out: dict[str, list[float]] = {}
    for top in events:
        end = top["ts"] + top["dur"]
        if top["name"] not in TRACED or end / 1e6 > opened_s:
            continue
        inside = children[top["args"]["id"]]
        if not inside:  # a lowering, most often: no scope is called under it
            continue
        row = out.setdefault(_label(top), [0.0, 0.0, 0.0])
        first = min(ev["ts"] for ev in inside)
        last = max(ev["ts"] + ev["dur"] for ev in inside)
        covered = sum(ev["dur"] for ev in inside)
        row[0] += (first - top["ts"]) / 1e6
        row[1] += (last - first - covered) / 1e6
        row[2] += (end - last) / 1e6
    return out


def device_by_scope(trace: dict, scopes: set[str]) -> dict[str, float]:
    """Share (%) of the window's device time by the innermost of
    ``scopes`` an operation lies under (``(none)``: under none of them):
    the device's side of a ``trace_part`` scope."""
    window = next((s for s in trace["spans"] if s[0] == "phase:window"), None)
    if window is None:
        return {}
    t0, t1 = window[1], window[1] + window[2]
    ns = collections.Counter()
    for _dev, _name, start, dur, scope in trace["ops"]:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            inside = [s for s in re.findall(r"magi_\w+", scope or "") if s in scopes]
            ns[inside[-1] if inside else "(none)"] += b - a
    total = sum(ns.values())
    return {s: 100.0 * v / total for s, v in ns.items()} if total else {}


def load(path: str):
    """(what ``run`` noted, the ring's spans) from ``<cell>.<seed>.json``
    and the ``dump_events`` file beside it."""
    with open(path) as f:
        noted = json.load(f)
    with open(path[: -len(".json")] + ".events.json") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    return noted, events


def show(path: str) -> None:
    d, events = load(path)
    began_s, opened_s = d["began_s"], d["began_s"] + d["setup_s"]
    rows, gaps, unspanned = waterfall(events, began_s, opened_s)
    print(f"## {d['cell']} seed {d['seed']}: setup_s {d['setup_s']:.2f}, "
          f"{len(events)} spans, {d['dropped']} dropped")
    boot = next((e for e in events if e["name"] == "process_boot"), None)
    if boot is not None:
        print(f"   process_boot whole {boot['dur'] / 1e6:.2f} s {boot['args']}")
    for label, (seconds, n) in sorted(rows.items(), key=lambda r: -r[1][0]):
        if seconds >= 0.05:
            print(f"   {seconds:8.2f} s  {label}" + (f" x{n}" if n > 1 else ""))
    small = sum(s for s, _n in rows.values() if s < 0.05)
    print(f"   {small:8.2f} s  (rows under 0.05 s)")
    print(f"   {unspanned:8.2f} s  unspanned; sum "
          f"{sum(s for s, _n in rows.values()) + unspanned:.2f}")
    for seconds, before, after in gaps[:6]:
        print(f"       gap {seconds:7.2f} s  after [{before}] before [{after}]")
    device = d.get("device_by_scope", {})
    places = unscoped_by_place(events, opened_s)
    for program, scopes in trace_by_scope(events, opened_s).items():
        total = sum(s for s, _n in scopes.values())
        if total < 0.5:
            continue
        print(f"   -- {program}: {total:.2f} s by part "
              "(self s, spans, device share of the window %)")
        for scope, (seconds, n) in sorted(scopes.items(), key=lambda r: -r[1][0]):
            share = device.get(scope)
            print(f"      {seconds:7.3f}  {n:4d}  "
                  f"{'' if share is None else format(share, '6.2f'):>6}  {scope}")
        if program in places:
            before, between, after = (round(x, 3) + 0.0 for x in places[program])
            print(f"      (unscoped) lies {before:.3f} s before the first part, "
                  f"{between:.3f} between parts, {after:.3f} after the last")
    metrics = {k: round(v["value"], 3) for k, v in d["metrics"].items()
               if v["unit"] == "s" or k.startswith("key_build")}
    print(f"   line: {json.dumps(metrics)}")


# ---------------------------------------------------------------------------
# run: one traced cell, the ring kept
# ---------------------------------------------------------------------------


def run(argv, *, allow_cpu: bool = False) -> int:
    """``allow_cpu`` is a rehearsal's, as ``harness.main``'s is: a keyword
    and no option of the command line."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", required=True)
    p.add_argument("--out", required=True)
    args, rest = p.parse_known_args(argv)  # the rest is benchmarks/run.py's
    from benchmarks import harness

    said = io.StringIO()  # the run's log and last line, passed on below
    try:
        with contextlib.redirect_stdout(said):
            rc = harness.main(
                ["--workload", args.workload, "--seed", args.seed,
                 "--trace", "1", *rest],
                allow_cpu=allow_cpu, t_start=_T_START,
            )
    finally:
        sys.stdout.write(said.getvalue())
        sys.stdout.flush()
    if rc:
        return rc
    lines = said.getvalue().strip().splitlines()
    (setup_s,) = re.findall(r"set-up took (\S+) s", "\n".join(lines))
    from magiattention_tpu import telemetry

    buffer = telemetry.get_event_buffer()
    scopes = {
        e["args"]["scope"] for e in buffer.events() if e["name"] == "trace_part"
    }
    with open(os.path.join(
        harness.CHECKOUT, ".bench_out", args.workload, "trace.json"
    )) as f:
        device = device_by_scope(json.load(f), scopes)
    os.makedirs(args.out, exist_ok=True)
    out = os.path.join(args.out, f"{args.workload}.{args.seed}")
    telemetry.dump_events(out + ".events.json")
    with open(out + ".json", "w") as f:
        json.dump(
            {
                "cell": args.workload, "seed": int(args.seed),
                # the metric's window: from the harness's first clock read
                "began_s": harness._T0, "setup_s": float(setup_s),
                "dropped": buffer.dropped, "device_by_scope": device,
                "metrics": json.loads(lines[-1])["metrics"],
            },
            f,
        )
    print(f"ring and device shares kept in {out}.json", file=sys.stderr)
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "run":
        sys.exit(run(sys.argv[2:]))
    if len(sys.argv) >= 3 and sys.argv[1] == "show":
        for path in sys.argv[2:]:
            show(path)
        sys.exit(0)
    sys.exit(__doc__)
