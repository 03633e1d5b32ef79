#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once, in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (native build if absent, compile cache, data from the seed, model on
the device, the first steps that the reference follows) is counted as
``setup_s``; then the loop runs for ``--seconds``; then the program's state is
freed and the plain reference decides ``correct``. The last line of standard
output is the result; a line that would not pass ``harness/result_line.py``
makes the run exit non-zero instead. No chip, no result: there is no CPU
fallback.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, CHECKOUT)

from harness import cells, check, result_line, trace  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_BAD_LINE = 4
EXIT_NO_PROGRAM = 5


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def find_chip(chips: int, require_chip: bool):
    """The devices the cell runs on, or exit: a run that finds no
    accelerator, or fewer chips than the cell asks for, prints no result."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            log(f"no accelerator: jax found platform {devs[0].platform!r}")
            sys.exit(EXIT_NO_CHIP)
        peaks_for(kind)  # an unknown chip is an error, not a default
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, jax found {len(devs)}")
        sys.exit(EXIT_NO_CHIP)
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _metric_value(snap, kind: str, name: str) -> float:
    return sum(m["value"] for m in snap[kind] if m["name"] == name)


def open_program(runner_name: str):
    """Import the program, point jax at the compile cache, build the native
    core if this checkout has none; returns the cell's runner module."""
    try:
        from dmlc_core_tpu.io import native
        from dmlc_core_tpu.tpu.runtime import enable_compile_cache
    except ImportError as e:
        log(f"the program is not in this directory: {e}")
        sys.exit(EXIT_NO_PROGRAM)
    import jax
    cache = enable_compile_cache()
    # with JAX_COMPILATION_CACHE_DIR set the program leaves jax's one-second
    # floor in place; the reference's and the check's small programs would
    # then compile anew in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache {cache}")
    native.lib()  # builds the native core on a checkout's first run
    log("native core ready")
    return cells.load_module("runners", runner_name)


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, spec=None, faults=None) -> dict:
    """Everything a run does but print: returns the result line. ``faults``
    is for the tests that break the timed path underneath: callables under
    ``before_data`` and ``after_build``, each given the session."""
    spec = spec or cells.load_spec()
    cell = cells.load_cell(spec, workload)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    devs = find_chip(int(cell["chips"]), require_chip)
    log(f"device {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}")
    runner = open_program(cfg["runner"])
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.tpu.runtime import compile_report

    s = runner.Session(cell, seed, len(devs))
    threads = max(1, min(8, (os.cpu_count() or 2) - 1))
    faults = faults or {}
    faults.get("before_data", lambda _: None)(s)
    s.write_data(threads)
    log(f"data written: {s.notes}")
    s.build()
    faults.get("after_build", lambda _: None)(s)
    log(f"model and iterator built; peak {memory_peak(devs)} bytes")
    program = s.first_steps()
    log(f"first steps: losses {program.losses}; peak {memory_peak(devs)} "
        f"bytes")
    compile_at_start = compile_report()

    # ---- the measured window
    snap0 = telemetry.snapshot(native=True)
    setup_s = time.perf_counter() - _T0
    plain_s = seconds / 2 if traced else seconds
    plain = runner.drive(s, plain_s)
    snap1 = telemetry.snapshot(native=True)
    sections = [plain]
    if traced:
        tdir = cells.cache_dir(cell["name"], "trace")
        tsec = runner.traced_drive(
            s, float(traffic.get("trace_window_s", 3.0)), tdir)
        sections.append(tsec)
    snap2 = telemetry.snapshot(native=True) if traced else snap1
    peak = memory_peak(devs)
    compile_at_end = compile_report()
    log(f"window done: {plain.steps} steps, {plain.rows} rows in "
        f"{plain.seconds:.3f}s; peak {peak} bytes")

    # ---- correctness, after the peak is read and the state freed
    s.free()
    log("program state freed")
    reference = s.reference_readings()
    numbers = check.gaps(program, reference)
    numbers.update(s.exact_numbers())
    numbers["compiles_in_window"] = (
        compile_at_end["backend_compiles"] + compile_at_end["cache_hits"]
        - compile_at_start["backend_compiles"]
        - compile_at_start["cache_hits"])
    numbers["new_shapes_in_window"] = (
        _metric_value(snap2, "gauges", "device_distinct_shapes")
        - _metric_value(snap0, "gauges", "device_distinct_shapes"))
    attempted = sum(sec.steps for sec in sections)
    failed = int(sum(sec.bad_losses for sec in sections) + sum(
        _metric_value(snap2, "counters", c)
        - _metric_value(snap0, "counters", c)
        for c in ("device_put_failures_total",
                  "rowblock_skipped_batches_total")))
    numbers["failed_batches"] = failed
    verdict = check.judge(numbers, cfg["limits"])
    log(f"reference losses {reference.losses}")

    # ---- metrics
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if not traced:
        values = dict(runner.end_to_end(plain), setup_s=setup_s)
    else:
        events = trace.load_xplane(trace.find_xplane(tdir))
        device.update(trace.busy(events, len(devs)))
        breakdown = trace.breakdown(events)
        ctx = {"plain": plain, "traced": tsec, "events": events,
               "telemetry": (snap0, snap1), "session": s, "cell": cell,
               "peaks": peaks_for(devs[0].device_kind) if require_chip
               else None, "device": device}
        values = {}
        for m in result_line.expected_metrics(spec, workload, True).values():
            how = cells.load_json("metrics", m["name"] + ".json")
            value = cells.load_module("readers", how["reader"]).read(ctx, how)
            if value is not None:  # a reader that finds nothing says nothing
                values[m["name"]] = value
    units = {m["name"]: m["unit"]
             for m in spec["per_layer" if traced else "end_to_end"]}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    ends = [plain.t0] + plain.step_end
    gaps = sorted(((b - a, i) for i, (a, b) in enumerate(zip(ends, ends[1:]))),
                  reverse=True)[:6]
    notes = {"setup": s.notes, "compile": compile_at_end,
             "turnovers": sum(len(sec.turnover_s) for sec in sections),
             "epochs_finished": len(s.epoch_rows),
             "turnover_s": plain.turnover_s,
             "turnover_at_step": plain.turnover_at,
             # [step, ms, of which: wait for the batch, dispatch, loss sync]
             "longest_steps_ms": [
                 [i, round(1e3 * g, 3)] + [round(1e3 * x, 3)
                                           for x in plain.phases[i]]
                 for g, i in gaps],
             "median_step_ms": 1e3 * statistics.median(
                 b - a for a, b in zip(ends, ends[1:]))}
    return result_line.build(verdict["ok"], attempted, failed, metrics,
                             device, breakdown, notes, verdict["numbers"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cells.load_spec()
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    spec=spec)
    try:
        result_line.validate(line, spec, args.workload, bool(args.trace))
    except result_line.LineError as e:
        log(f"the result line would be refused: {e}")
        log(json.dumps(line)[:4000])
        return EXIT_BAD_LINE
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(result_line.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
