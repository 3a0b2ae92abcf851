"""Functional: unified observability end to end (``obs/``).

The two hard contracts from docs/OBSERVABILITY.md, both tier-1:

* **transparency** — a run with every sink armed (trace + events +
  metrics + JSON logs) writes stores bitwise identical to an
  unobserved run: obs hooks watch host-side control flow and never
  touch the jitted programs;
* **coverage** — a supervised multi-restart chaos run produces ONE
  schema-valid Chrome trace covering the
  compile/step_round/io/checkpoint/drain driver phases and ONE merged
  event stream containing both the injected fault and the supervisor's
  recovery, validated by ``scripts/gs_report.py --check`` exactly as
  CI's chaos_smoke does.

The ``-m slow`` overhead guard bounds the cost of the whole apparatus:
the obs-on step loop stays within 3% of obs-off on the CPU host.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from test_async_io import _assert_trees_byte_identical
from test_end_to_end import run_cli, write_config

from grayscott_jl_tpu.obs.events import parse_events
from grayscott_jl_tpu.obs.trace import validate_trace

REPO = Path(__file__).resolve().parents[2]

STEPS = 60

OBS_ENV_KEYS = ("GS_TRACE", "GS_EVENTS", "GS_METRICS", "GS_METRICS_PROM",
                "GS_LOG_FORMAT")


def _obs_env(d):
    return {
        "GS_TRACE": str(d / "trace.json"),
        "GS_EVENTS": str(d / "events.jsonl"),
        "GS_METRICS": str(d / "metrics.jsonl"),
        "GS_METRICS_PROM": str(d / "prom.txt"),
        "GS_TPU_STATS": str(d / "stats.json"),
    }


def _run(tmp_path, name, extra_env=None, **config_kw):
    d = tmp_path / name
    d.mkdir()
    kw = dict(noise=0.1, steps=STEPS, output="gs.bp",
              checkpoint="true", checkpoint_freq=20)
    kw.update(config_kw)
    cfg = write_config(d, **kw)
    res = run_cli(d, cfg, extra_env=extra_env)
    return d, res


def test_stores_bitwise_identical_with_full_obs(tmp_path):
    """The transparency contract: GS_TRACE + GS_METRICS + GS_EVENTS +
    JSON logs on, or a profiler capture open, vs everything off —
    byte-identical stores."""
    off, res_off = _run(tmp_path, "off")
    assert res_off.returncode == 0, res_off.stderr + res_off.stdout

    on_dir = tmp_path / "on"
    on_dir.mkdir()
    cfg = write_config(on_dir, noise=0.1, steps=STEPS, output="gs.bp",
                       checkpoint="true", checkpoint_freq=20)
    env = {**_obs_env(on_dir), "GS_LOG_FORMAT": "json",
           "GS_METRICS_INTERVAL_S": "0.05"}
    res_on = run_cli(on_dir, cfg, extra_env=env)
    assert res_on.returncode == 0, res_on.stderr + res_on.stdout

    for store in ("gs.bp", "gs.vtk", "ckpt.bp"):
        _assert_trees_byte_identical(off / store, on_dir / store)

    # every sink actually produced its artifact
    for f in ("trace.json", "events.jsonl", "metrics.jsonl", "prom.txt",
              "stats.json"):
        assert (on_dir / f).exists(), f

    # JSON log mode: every stdout line parses
    for line in res_on.stdout.strip().splitlines():
        rec = json.loads(line)
        assert {"ts", "level", "proc", "msg"} <= set(rec)

    # interval flushing produced >= 2 records (0.05s over a multi-second
    # run) and the prometheus dump carries the step histogram
    records = [json.loads(ln) for ln in
               (on_dir / "metrics.jsonl").read_text().splitlines()]
    assert len(records) >= 2
    assert "step_latency_us" in (on_dir / "prom.txt").read_text()

    # A whole-run profiler capture (GS_TPU_PROFILE) with GS_TRACE unset:
    # stores still byte-identical, and the capture's host plane holds
    # the program's spans — the RunStats phases, the simulation's rounds.
    prof = tmp_path / "prof"
    prof.mkdir()
    cfg = write_config(prof, noise=0.1, steps=STEPS, output="gs.bp",
                       checkpoint="true", checkpoint_freq=20)
    res = run_cli(prof, cfg,
                  extra_env={"GS_TPU_PROFILE": str(prof / "xprof")})
    assert res.returncode == 0, res.stderr + res.stdout
    for store in ("gs.bp", "gs.vtk", "ckpt.bp"):
        _assert_trees_byte_identical(off / store, prof / store)
    from jax.profiler import ProfileData

    (xplane,) = (prof / "xprof").glob("plugins/profile/*/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"gs.compute", "gs.device_to_host", "gs.output",
            "gs.checkpoint", "gs.iterate", "gs.sync"} <= names, names


def test_supervised_chaos_run_single_merged_timeline(tmp_path):
    """The acceptance scenario: a supervised run eating a preemption
    AND a hang restarts twice; the single trace file validates against
    the Chrome schema with all five driver phases covered, and the
    single event stream tells the whole fault+recovery story."""
    d = tmp_path / "chaos"
    d.mkdir()
    cfg = write_config(d, noise=0.1, steps=STEPS, output="gs.bp",
                       checkpoint="true", checkpoint_freq=20)
    env = {
        **_obs_env(d),
        "GS_SUPERVISE": "1",
        "GS_MAX_RESTARTS": "5",
        "GS_RESTART_BACKOFF_S": "0.01",
        "GS_FAULTS": "step=25:kind=preempt;step=45:kind=hang",
        "GS_WATCHDOG": "on",
        "GS_WATCHDOG_STEP_ROUND_S": "3",
        "GS_HANG_BOUND_S": "40",
        # The device-side flight recorder rides the same chaos run:
        # per-boundary numerics + drift on the stream, per-compile
        # executable analytics, compile spans — all of it must
        # survive two supervised restarts as ONE merged story.
        "GS_NUMERICS": "boundary",
        "GS_XSTATS": "1",
    }
    res = run_cli(d, cfg, extra_env=env)
    assert res.returncode == 0, res.stderr + res.stdout

    # -- trace: valid, one file, all driver phases present
    doc = json.loads((d / "trace.json").read_text())
    assert validate_trace(doc) == []
    spans = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"compile", "step_round", "io", "checkpoint",
            "drain"} <= spans, spans
    # the nested RunStats spans ride along on their own tracks, and
    # the simulation's own round and compile spans inside them
    assert {"compute", "device_to_host"} <= spans
    assert {"iterate", "sync", "compile"} <= spans, spans
    # the watchdog expiry left its instant marker
    assert any(e["ph"] == "i" and e["name"] == "watchdog_expired"
               for e in doc["traceEvents"])

    # -- events: ONE stream holds both faults and both recoveries
    events = parse_events(str(d / "events.jsonl"))
    kinds = [e["kind"] for e in events]
    injected = [e["attrs"]["fault"] for e in events
                if e["kind"] == "injected"]
    assert set(injected) == {"preempt", "hang"}
    recovered = [e["attrs"]["fault"] for e in events
                 if e["kind"] == "recovery"]
    assert recovered == ["preemption", "hang"]
    assert kinds.count("run_start") == 3  # original + two restarts
    assert "hang" in kinds        # the watchdog's stack-dump event
    assert "run_complete" in kinds
    # per-attempt phase attribution for gs_report
    attempts = [e["attrs"]["attempt"] for e in events
                if e["kind"] == "attempt_phases"]
    assert attempts == [0, 1]
    # schema: flat six-field records throughout
    for e in events:
        assert set(e) == {"ts", "proc", "kind", "phase", "step",
                          "attrs"}

    # -- flight recorder: numerics records at every write boundary,
    #    executable analytics per compile — on the SAME stream
    num_events = [e for e in events if e["kind"] == "numerics"]
    assert num_events, kinds
    assert all(set(e["attrs"]["fields"]) == {"u", "v"}
               for e in num_events)
    exe_events = [e for e in events if e["kind"] == "executable"]
    assert exe_events and all(
        "compile_s" in e["attrs"] for e in exe_events
    )

    # -- stats: metrics + obs provenance merged, attempt-tagged
    stats = json.loads((d / "stats.json").read_text())
    assert stats["config"]["attempt"] == 2
    assert stats["watchdog"]["attempt"] == 2
    names = {m["name"] for m in stats["metrics"]["counters"]}
    assert {"steps", "restarts", "io_steps_written",
            "compiles"} <= names
    hist = next(h for h in stats["metrics"]["histograms"]
                if h["name"] == "step_latency_us")
    assert hist["count"] > 0 and hist["p50"] is not None
    assert stats["obs"]["trace"]["enabled"] is True
    assert any(e["event"] == "attempt_phases" for e in stats["faults"])

    # -- stats: numerics section (per-boundary stats + drift) and the
    #    executables section (cost/memory per compile)
    num = stats["numerics"]
    assert num["mode"] == "boundary" and num["probes"] > 0
    assert set(num["last"]["fields"]) == {"u", "v"}
    assert num["max_drift"]  # a chaos run's fields move
    ex = stats["executables"]
    assert ex["compiles"] >= 1 and ex["records"]
    rec = ex["records"][0]
    assert rec["compile_s"] > 0 and rec["cost"]["flops"] > 0
    # each attempt's compile span names its runner and step (and,
    # where a persistent cache is armed, its outcome, as the record)
    compiles = [e["args"] for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"] == "compile"
                and "runner" in e["args"]]
    assert {a["runner"] for a in compiles} == {
        r["name"] for r in ex["records"]}, compiles
    assert sorted(a["step"] for a in compiles) == [0, 20, 40], compiles
    gauges = {g["name"] for g in stats["metrics"]["gauges"]}
    assert {"numerics_mean", "numerics_drift"} <= gauges
    # no model projection is exported as a measurement
    assert not {"comm_hidden_us_per_step", "comm_exposed_us_per_step",
                "model_projected_step_us",
                "model_vs_measured_residual_us"} & gauges
    assert "model_vs_measured_residual_us" not in ex

    # -- gs_report --check agrees (the CI entry point)
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "gs_report.py"),
         "--check", "--trace", str(d / "trace.json"),
         "--events", str(d / "events.jsonl"),
         "--stats", str(d / "stats.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "OK" in proc.stdout


def test_autotune_decision_reaches_event_stream(tmp_path):
    """Auto dispatch under GS_EVENTS: the tuning decision (cache
    hit/miss, source) lands on the same timeline as everything else."""
    d = tmp_path / "auto"
    d.mkdir()
    cfg = write_config(d, noise=0.1, steps=20,
                       kernel_language="Auto")
    env = {"GS_EVENTS": str(d / "events.jsonl"), "GS_AUTOTUNE": "cached",
           "GS_AUTOTUNE_CACHE": str(d / "tunecache")}
    res = run_cli(d, cfg, extra_env=env)
    assert res.returncode == 0, res.stderr + res.stdout
    events = parse_events(str(d / "events.jsonl"))
    (tune,) = [e for e in events if e["kind"] == "autotune"]
    assert tune["phase"] == "compile"
    assert tune["attrs"]["mode"] == "cached"
    assert tune["attrs"]["cache"] == "miss"


@pytest.mark.parametrize("model",
                         ["grayscott", "brusselator", "fhn", "heat"])
def test_flight_recorder_transparency_all_models(tmp_path, model):
    """The flight-recorder transparency contract, every registered
    model: GS_NUMERICS=every_round (the most intrusive mode — a
    probe-only jit after every round) plus GS_XSTATS (runners routed
    through the instrumented AOT compile) write stores bitwise
    identical to an unobserved run."""

    def model_cfg(d):
        lines = [
            "L = 16", "steps = 12", "plotgap = 4", "noise = 0.1",
            'output = "gs.bp"', "checkpoint = true",
            "checkpoint_freq = 6", 'checkpoint_output = "ckpt.bp"',
            'precision = "Float32"', 'backend = "CPU"',
            'kernel_language = "Plain"',
            "dt = 1.0" if model == "grayscott" else "dt = 0.05",
            "[model]", f'name = "{model}"',
        ]
        p = d / "config.toml"
        p.write_text("\n".join(lines) + "\n")
        return p

    off = tmp_path / "off"
    off.mkdir()
    res = run_cli(off, model_cfg(off))
    assert res.returncode == 0, res.stderr + res.stdout

    on = tmp_path / "on"
    on.mkdir()
    env = {
        "GS_NUMERICS": "every_round",
        "GS_XSTATS": "1",
        "GS_EVENTS": str(on / "events.jsonl"),
        "GS_METRICS": str(on / "metrics.jsonl"),
        "GS_TPU_STATS": str(on / "stats.json"),
    }
    res = run_cli(on, model_cfg(on), extra_env=env)
    assert res.returncode == 0, res.stderr + res.stdout

    for store in ("gs.bp", "gs.vtk", "ckpt.bp"):
        _assert_trees_byte_identical(off / store, on / store)

    # the probes really ran, with the model's own field names
    from grayscott_jl_tpu import models

    stats = json.loads((on / "stats.json").read_text())
    fields = set(models.get_model(model).field_names)
    assert set(stats["numerics"]["last"]["fields"]) == fields
    assert stats["numerics"]["probes"] >= 3  # every round
    assert stats["executables"]["compiles"] >= 1


#: Worker for the 2-process rank-merge test: bring up jax.distributed
#: over a localhost coordinator (the REAL 2-process path the
#: KV-rendezvous consensus test uses — no XLA collectives needed) and
#: write events + metrics through the process-wide singletons, whose
#: paths rank-suffix because process_count() == 2.
_RANK_WORKER = """\
import os, time
import jax

jax.distributed.initialize(
    coordinator_address=os.environ["GS_TPU_COORDINATOR"],
    num_processes=int(os.environ["GS_TPU_NUM_PROCESSES"]),
    process_id=int(os.environ["GS_TPU_PROCESS_ID"]),
)
from grayscott_jl_tpu.obs.events import get_events
from grayscott_jl_tpu.obs.metrics import get_metrics

pid = jax.process_index()
es = get_events()
es.emit("run_start", step=0, attempt=0, model="grayscott", L=16,
        steps=10, kernel="xla", mesh=[1, 1, 1], restart=False)
time.sleep(0.05 * (pid + 1))  # deterministic cross-rank time order
es.emit("output", phase="io", step=10, output_step=1)
m = get_metrics()
m.counter("steps").inc(10 + pid)
m.histogram("step_latency_us").observe(100.0 + pid)
m.maybe_flush(force=True)
print("OBSOK", es.path)
"""


def test_two_process_rank_merged_report(tmp_path):
    """Multi-rank stream merging end to end: a real 2-process run
    (jax.distributed over a localhost coordinator) writes
    ``.rank<N>``-suffixed GS_EVENTS/GS_METRICS files; gs_report.py
    --check validates them and the rendered report is ONE ordered,
    per-proc-attributed timeline."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    events_path = tmp_path / "events.jsonl"
    metrics_path = tmp_path / "metrics.jsonl"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": str(REPO) + os.pathsep
            + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "GS_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "GS_TPU_NUM_PROCESSES": "2",
            "GS_TPU_PROCESS_ID": str(pid),
            "GS_EVENTS": str(events_path),
            "GS_METRICS": str(metrics_path),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _RANK_WORKER], cwd=tmp_path,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, out + err
        assert "OBSOK" in out

    # the singletons rank-suffixed their paths; nothing wrote the bare one
    assert not events_path.exists()
    for rank in (0, 1):
        assert (tmp_path / f"events.jsonl.rank{rank}").exists()
        assert (tmp_path / f"metrics.jsonl.rank{rank}").exists()

    # reader-side join: one time-ordered, per-proc-attributed list
    from grayscott_jl_tpu.obs.events import parse_events_multi

    merged = parse_events_multi(str(events_path))
    assert sorted(e["proc"] for e in merged) == [0, 0, 1, 1]
    assert [e["ts"] for e in merged] == sorted(
        e["ts"] for e in merged
    )

    # --check accepts the rank families; the report renders one
    # timeline with a proc column and a per-proc metrics summary
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "gs_report.py"),
         "--check", "--events", str(events_path),
         "--metrics", str(metrics_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "gs_report.py"),
         "--events", str(events_path),
         "--metrics", str(metrics_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "p0 " in proc.stdout and "p1 " in proc.stdout
    assert "proc 0" in proc.stdout and "proc 1" in proc.stdout


@pytest.mark.slow
def test_obs_overhead_within_three_percent(tmp_path):
    """The cost guard: the fully-instrumented step loop stays within 3%
    of the uninstrumented one (min-of-3 wall each way, CPU host)."""

    def measure(name, extra_env):
        walls = []
        for i in range(3):
            d = tmp_path / f"{name}{i}"
            d.mkdir()
            cfg = write_config(d, noise=0.1, steps=300, plotgap=10,
                               output="gs.bp", checkpoint="true",
                               checkpoint_freq=50)
            env = dict(extra_env)
            env["GS_TPU_STATS"] = str(d / "stats.json")
            res = run_cli(d, cfg, extra_env=env)
            assert res.returncode == 0, res.stderr + res.stdout
            walls.append(
                json.loads((d / "stats.json").read_text())["wall_s"]
            )
        return min(walls)

    off = measure("off", {})
    on_env = {k: str(tmp_path / f"on.{k.lower()}") for k in
              ("GS_TRACE", "GS_EVENTS", "GS_METRICS")}
    on_env["GS_METRICS_INTERVAL_S"] = "0.1"
    on = measure("on", on_env)
    # 3% relative plus a 50ms absolute floor so sub-second timer jitter
    # cannot fail a run whose real overhead is microseconds/boundary.
    assert on <= off * 1.03 + 0.05, (on, off)
