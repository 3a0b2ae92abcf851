#!/usr/bin/env python3
"""Chip benchmark of grayscott_jl_tpu: one cell, one run.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and its files under
``perfbench/``, refuses to run without a TPU (no CPU fallback), sets
the cell up, measures for ``--seconds`` seconds, compares what the
window produced with the plain reference, and prints one JSON line as
the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window with the JAX
profiler and reports its per-layer metrics.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def _age_at_start() -> float:
    """Seconds this process had lived when ``_T0`` was read (from
    ``/proc``, 10 ms resolution; 0 where it cannot be read)."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK")
                   - (time.perf_counter() - _T0))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE0 = _age_at_start()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def setup_seconds() -> float:
    """Seconds from process start to now."""
    return _AGE0 + time.perf_counter() - _T0


def traced_record(cell, run, win, chips, device_kind) -> dict:
    """What the per-layer readers read beside the trace."""
    from perfbench import trace_reduce

    record = run.extra_record()
    record.update(
        L=run.settings.L, chips=chips, steps=win["steps"],
        window_s=win["wall_s"],
        runner_compile_s=run.spans["construct"] + run.spans["first_round"],
        executables=run.info["executables"],
        peaks=trace_reduce.peaks(device_kind),
        flops_per_cell_update=float(
            cell["config_table"]["bench"]["flops_per_cell_update"]),
    )
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness as H

    bench = H.load_benchmark()
    cell = H.load_cell(args.workload, bench)
    chips = int(cell["chips"])

    # The program's TPU runs get the split-phase exchange flags from
    # driver.main before libtpu starts; the benchmark gives them the
    # same, with the program's own function.
    from grayscott_jl_tpu.utils.benchmark import inject_overlap_tpu_flags

    inject_overlap_tpu_flags()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        H.log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s). No result.")
        return 3
    devices = devices[:chips]
    t_backend = setup_seconds()

    settings = H.build_settings(cell)
    run = H.make_cell(cell, settings, args.seed, chips)
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        # The trace has the GS_TPU_STATS record of each run_once.
        run.stats_path = os.path.join(trace_dir, "stats.json")
        os.environ["GS_TPU_STATS"] = run.stats_path
    run.setup()
    setup_s = setup_seconds()
    H.log(f"setup_s {setup_s:.3f}: backend {t_backend:.3f}, "
          + ", ".join(f"{k} {v:.3f}" for k, v in run.spans.items()))
    for rec in run.info["executables"]:
        H.log(f"runner {rec.get('name')}: compile_s {rec.get('compile_s')}"
              f" cache {rec.get('cache')}")

    if args.trace:
        from perfbench import trace_reduce

        trace_reduce.start(trace_dir)
    win = run.window(args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices)
    if args.trace:
        record = traced_record(cell, run, win, chips, devices[0].device_kind)
    run.free()
    gc.collect()

    t_check = time.perf_counter()
    checks = run.check(devices)
    H.log(f"check {time.perf_counter() - t_check:.3f} s")
    correct, judged = H.judge(checks, cell["limits"])

    L = settings.L
    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    breakdown = None
    if args.trace:
        red = trace_reduce.load(trace_dir, n_devices=chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in cell["per_layer"]:
            value = H.load_reader(m["name"])(red, record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
    else:
        for name in cell["end_to_end"]:
            if name == "cell_updates_per_s":
                value = L**3 * win["steps"] / win["wall_s"]
            elif name == "setup_s":
                value = setup_s
            metrics[name] = {"value": value,
                             "unit": next(m["unit"] for m in bench[
                                 "end_to_end"] if m["name"] == name)}

    H.log(f"window: {win['attempted']} rounds or runs, "
          f"{win['steps']} steps in {win['wall_s']:.3f} s; "
          f"kernel {run.info['kernel_language']}, mesh "
          f"{run.info['mesh_dims']}")
    for name, c in judged.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = judged
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
