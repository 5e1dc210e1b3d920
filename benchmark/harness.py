"""The benchmark harness: one run of one cell.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
looks the cell up in ``BENCHMARK.json``, loads its configuration
(``benchmark/configs/<config>.json``) and traffic
(``benchmark/traffic/<traffic>.json``), and drives the study kind the
configuration names (``benchmark/kinds/<kind>.py``):

1. set-up: JAX on the chip with the persistent compile cache in the
   checkout's ``.jax_cache/``, then one warm-up study of the cell's own
   scenario, which compiles or loads every program the window runs;
   ``setup_s`` is the process's age at its end;
2. the window: studies back to back, each built anew from the configuration
   and the seed, until ``--seconds`` have passed; the window ends with the
   study that crosses that mark, and ``study_s`` is the mean wall time of
   the studies completed in it, each from its scenario's construction to its
   result on the host.  A study counts as failed when it raises, compiles or
   traces a program, or did not do its own work (the kind says what that
   is);
3. with ``--trace 1`` the window runs under the profiler, each study marked
   by a ``TraceAnnotation``, and the per-layer metrics are read from the
   program's spans and the reduced trace by ``benchmark/metrics/<name>.py``;
4. after the window: the device's peak memory, then the reference comparison
   that decides ``correct``.  Each compared number and its limit is printed
   as the last lines of standard error and, under ``checks``, as the last key
   of the result line, the last line of standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
import traceback

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

#: JAX's monitoring event for one backend (XLA) compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class StudyRecord:
    """One study of the window: its wall time and the program's spans."""

    index: int
    wall_s: float
    tel: object  # repro.obs.Telemetry
    root: object  # its bench.study span
    annotation: str
    compiled: int  # programs traced or compiled inside it


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader reads."""

    studies: list[StudyRecord]
    trace: dict | None
    shapes: dict
    peaks: dict


def startup_s(t0: float) -> float:
    """Seconds from this process's start to ``t0`` on the wall clock, read
    from ``/proc`` to its 10 ms (0 where ``/proc`` is not there)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return 0.0
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK") - (time.time() - t0))


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_inputs(bench: dict, workload: str, root: pathlib.Path = ROOT):
    """``(cell, config, traffic, end_to_end, per_layer)`` of one cell: its
    entry, its configuration and traffic files, and the metrics it reports."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"] if applies(m)]
    return cell, config, traffic, e2e, per_layer


def device_info(chips: int, require_chip: bool = True) -> dict:
    """The devices as JAX reports them; with ``require_chip``, raises
    :class:`NoChip` unless there are ``chips`` accelerators (no CPU fallback)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform == "cpu" or len(devices) < chips):
        raise NoChip(f"JAX found {len(devices)} {dev.platform} device(s) ({dev.device_kind}); "
                     f"the cell needs {chips} accelerator chip(s) and has no CPU fallback")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def configure_jax() -> None:
    """The persistent compile cache at its fixed place in the checkout, every
    program kept however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event == COMPILE_EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def one_study(study, index: int, compiles: CompileCounter, annotate: bool):
    """Run one study; returns ``(record, output, why)``: ``why`` says why it
    did not do its own work (None when it did), ``record.compiled`` how many
    programs it traced or compiled."""
    import jax

    from repro.obs import Telemetry, retrace_guard

    tel = Telemetry()
    name = f"bench.study {index}"
    before = compiles.count
    guard = retrace_guard(None, allow=None)
    ann = jax.profiler.TraceAnnotation(name) if annotate else contextlib.nullcontext()
    out, why = None, None
    with ann, tel, guard, tel.span("bench.study") as root:
        try:
            out = study.run(tel)
        except Exception:  # a study that raises is failed, not fatal to the run
            why = "raised:\n" + traceback.format_exc()
    if why is None:
        why = study.own_work(tel, out)
    compiled = guard.new_traces + compiles.count - before
    return StudyRecord(index, root.dur, tel, root, name, compiled), out, why


def run_cell(bench, workload, seed, seconds, trace, device, t0, require_chip=True,
             log=sys.stderr) -> dict:
    """One run of ``workload``; returns the result line as a dict."""
    from benchmark import roofline, trace_reduce

    cell, config, traffic, e2e, per_layer = cell_inputs(bench, workload)
    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    study = kind.Study(config, traffic, seed)
    peaks = roofline.peaks(device["kind"]) if require_chip else {}
    compiles = CompileCounter()

    startup = startup_s(t0)
    warm, _, why = one_study(study, -1, compiles, annotate=False)
    setup_s = startup + time.time() - t0
    print(f"set-up {setup_s:.3f} s: warm-up study {warm.wall_s:.3f} s, {warm.compiled} "
          f"program(s) traced or compiled, compiles taking {compiles.seconds:.3f} s"
          + (f"; the warm-up did not do its own work: {why}" if why else ""), file=log, flush=True)

    if trace:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from annotations, not every call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    records, kept, walls, failures = [], [], [], 0
    w0 = time.perf_counter()
    while True:
        record, out, why = one_study(study, len(records), compiles, annotate=bool(trace))
        records.append(record)
        if why is None and record.compiled:
            why = f"{record.compiled} program(s) traced or compiled inside the window"
        if out is not None:
            walls.append(record.wall_s)
            kept.append(study.keep(out))
        if why:
            failures += 1
            print(f"study {record.index} failed: {why}", file=log, flush=True)
        del out
        if time.perf_counter() - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    print(f"window {window_s:.3f} s, study walls (s): "
          f"{[round(r.wall_s, 4) for r in records]}", file=log, flush=True)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = _reduce_trace(trace_reduce, records)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    device = dict(device, memory_peak_bytes=_memory_peak())
    gc.collect()

    checks = study.checks(kept) if kept else {}
    correct = bool(kept) and all(checks[k] <= kind.LIMITS[k] for k in kind.LIMITS)
    result = {"correct": correct, "attempted": len(records), "failed": failures}
    if not trace:
        # the studies' own walls: the harness's bookkeeping between studies
        # (keeping what the comparison reads) is no part of a study
        values = {"study_s": sum(walls) / len(walls) if walls else float("nan"),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in e2e}
    else:
        data = RunData(records, reduced, study.shapes(kept), peaks)
        metrics = {}
        for m in per_layer:
            value = load_reader(m["name"]).read(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": checks.get(k), "limit": kind.LIMITS[k]} for k in kind.LIMITS}
    for k in kind.LIMITS:
        print(f"check {k}: {checks.get(k)} (limit {kind.LIMITS[k]})", file=log, flush=True)
    return result


def load_reader(name: str):
    """``benchmark/metrics/<name>.py``, whose ``read(RunData)`` returns the
    metric or None when the run holds nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"benchmark.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reduce_trace(trace_reduce, records) -> dict:
    """The window's trace, reduced; program spans are put on the trace's
    clock by each study's annotation."""
    events = trace_reduce.load(TRACE_DIR)
    marks = {name: (s, e) for name, s, e in trace_reduce.annotations(events, "bench.study ")}
    spans = []
    for r in records:
        if r.annotation not in marks:
            continue
        start_ns = marks[r.annotation][0]
        for s in r.tel.iter_spans():
            a = start_ns + int((s.t0 - r.root.t0) * 1e9)
            spans.append((s.name, a, a + int(s.dur * 1e9)))
    inside = [marks[r.annotation] for r in records if r.annotation in marks]
    window = (min(s for s, _ in inside), max(e for _, e in inside))
    return trace_reduce.reduce(events, window, spans)


def _memory_peak() -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def parse(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv, t0: float) -> int:
    args = parse(argv)
    try:
        importlib.import_module("repro")
    except ImportError as e:
        print(f"the program is not in this checkout (src/repro): {e}", file=sys.stderr)
        return 2
    bench = load_benchmark()
    cell = cell_inputs(bench, args.workload)[0]
    try:
        device = device_info(int(cell["chips"]))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    configure_jax()
    print(f"device ready {startup_s(t0) + time.time() - t0:.3f} s after process start",
          file=sys.stderr, flush=True)
    result = run_cell(bench, args.workload, args.seed, args.seconds, args.trace, device, t0)
    print(json.dumps(result), flush=True)
    return 0
