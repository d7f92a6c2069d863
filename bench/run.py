"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``, ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the configuration names its deployment,
the module that brings the generator, the program's build, the plain
reference and the comparison (``bench/deployment.py``).  One process:
set-up (the generator's host pool from the seed, the program built from
the configuration, two warm-up ticks that compile or load the step),
then a window of ``--seconds`` through the program's normal entry
points, then the plain reference over every tick and the comparison
that decides ``correct``.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` a JAX profiler trace of the last
seconds of the window, and the program's spans and the ticks before it,
give its per-layer metrics, each read by ``bench/metrics/<name>.py``.
The numbers compared and their limits are the last lines of standard
error and the last key of the result line.  Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell needs, or
where the program under test (``src/repro``) is missing.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import queue
import re
import resource
import shutil
import sys
import threading
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: seconds at the end of the window a ``--trace 1`` run records in the
#: profiler
TRACE_SECONDS = 3.0
#: share of the window's ticks, drawn from the seed, that the comparison
#: sees in full (the deployment's ``trim`` with ``full``); the others it
#: sees as ``trim`` keeps them
FULL_SHARE = 1 / 16
#: warm-up ticks, run through the same step before the window
WARMUP_TICKS = 2


def load_json(*parts: str) -> dict:
    return json.loads(BENCH.joinpath(*parts).read_text())


def cell_spec(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload, config, traffic) of a cell, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise SystemExit(f"bench/run.py: no workload {name!r} in "
                         f"BENCHMARK.json")
    return (bench, wl, load_json("configs", wl["config"] + ".json"),
            load_json("traffic", wl["traffic"] + ".json"))


def metrics_of(entries: list[dict], cell: str) -> list[dict]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx) -> float | None:
    """Run ``read(ctx)`` of ``bench/metrics/<name>.py`` or, where there
    is none, of the reader shared by a metric's splits: ``cells_ms.py``
    for ``cells_ms.steady`` and ``cells_ms.sat``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Producer:
    """The generator on a thread of its own, a couple of ticks ahead,
    so that making rows never holds up the tick loop."""

    def __init__(self, gen, first: int):
        self.q: queue.Queue = queue.Queue(maxsize=2)
        self.stop = threading.Event()
        self.made_s: list[float] = []       # seconds to make each batch
        self.thread = threading.Thread(target=self._run, args=(gen, first),
                                       daemon=True)
        self.thread.start()

    def _run(self, gen, t):
        while not self.stop.is_set():
            t0 = time.perf_counter()
            item = (t, *gen.batch(t))
            self.made_s.append(time.perf_counter() - t0)
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            t += 1

    def get(self):
        return self.q.get()

    def close(self):
        self.stop.set()
        self.thread.join()


def wait_until(t: float) -> None:
    """Sleep to within half a millisecond of t, then spin."""
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if left > 0.0015:
            time.sleep(left - 0.001)


def drive(sysm, item, producer, first: int, rows: int, open_loop: bool,
          rate: float, seconds: float, span, on_tick=None, phases=None):
    """The measured window: ticks from ``item`` on, through the system,
    for ``seconds``.  Open loop: tick t is due when the last of its
    events has been created (event i at ``start + (i - first * rows) /
    rate``) and goes out then, or at once when the system is behind;
    the window holds the ticks due before its end.  Closed loop: the
    next tick goes out as soon as the last one's outputs are on the
    host, until the window ends.  Returns ([(tick, due, dispatched,
    emitted)], start, end, the next pending item).  ``phases``, where
    given, receives each tick's (wait for the generator before it,
    then the seconds of each of ``sysm.phase_names``)."""
    ticks = []
    start = time.perf_counter()
    end = start + seconds
    waited = 0.0
    while True:
        t, items, ts = item
        if open_loop:
            due = start + ((t - first + 1) * rows - 1) / rate
            if due > end:
                break
            wait_until(due)
        elif time.perf_counter() >= end:
            break
        disp = time.perf_counter()
        with span("bench.tick"):
            out = sysm.step(items, ts, span)
        emit = time.perf_counter()
        ticks.append((t, due if open_loop else disp, disp, emit))
        if phases is not None:
            marks = (disp, *sysm.marks)
            phases.append((waited, *(b - a for a, b in
                                     zip(marks, marks[1:]))))
        if on_tick is not None:
            on_tick(t, out, len(ticks), emit - start)
        got = time.perf_counter()
        item = producer.get()
        waited = time.perf_counter() - got
    return ticks, start, end, item


class Ctx:
    """What a per-layer metric reader sees."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(bench: dict, wl: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, lane: dict | None = None,
             log=print, info: dict | None = None
             ) -> tuple[dict, list[str]]:
    """Set-up, window, reference, comparison and metrics of one run.
    Returns (result line, check lines).  ``lane`` lets a CPU test run
    the same path on the fused tick's jnp lane; ``info``, where given,
    receives both sides' end-of-run counters.  The configuration's
    matmul precision holds inside the run only."""
    import jax

    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        return _run_cell(bench, wl, cfg, traffic, seed, seconds, trace,
                         lane, log, info)


def _run_cell(bench, wl, cfg, traffic, seed, seconds, trace, lane, log,
              info):
    import jax
    import numpy as np

    from bench import deployment
    from bench import trace_reduce as TR
    from repro.obs.trace import Tracer

    dep = deployment.load(cfg)
    b, shards = dep.rows_per_tick(cfg), cfg["shards"]
    marks = [("imports and devices", time.perf_counter())]
    gen = dep.Generator(cfg, traffic, seed)
    marks.append(("host pool", time.perf_counter()))
    tracer = Tracer() if trace else None
    sysm = dep.build(cfg, tracer, lane)
    marks.append(("program built", time.perf_counter()))
    nullspan = contextlib.nullcontext()

    def span(name):
        return jax.profiler.TraceAnnotation(name) if trace else nullspan

    keep_full = np.random.default_rng(
        deployment.seed_sequence(seed, 2)).random(1 << 20) < FULL_SHARE
    # tick -> (compared in full, each shard's trimmed outputs)
    outs: dict[int, tuple[bool, list[dict]]] = {}
    for t in range(WARMUP_TICKS):
        items, ts = gen.batch(t)
        outs[t] = (True, [dep.trim(o, True)
                          for o in sysm.step(items, ts, span)])
        marks.append((f"warm-up tick {t}", time.perf_counter()))
    compiled_before = sysm.compiled()
    log("set-up: " + ", ".join(
        f"{name} {t1 - t0:.2f} s" for (_, t0), (name, t1) in
        zip([("", T_START)] + marks, marks))
        + f"; {compiled_before} executables of the step", file=sys.stderr)
    compiles = []

    def on_event(event, *args, **kw):
        if "compil" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    producer = Producer(gen, WARMUP_TICKS)
    first = WARMUP_TICKS
    open_loop = traffic["loop"] == "open"
    rate = float(traffic["rate"])
    trace_dir = ROOT / ".bench_out" / f"trace-{os.getpid()}"
    # a traced run records the last TRACE_SECONDS of its window; the
    # host spans and ticks before that are the unprofiled ones
    profiled = {"from": None, "at": None}

    def on_tick(t, out, n, elapsed):
        full = bool(keep_full[t])
        outs[t] = (full, [dep.trim(o, full) for o in out])
        if trace and profiled["from"] is None \
                and elapsed >= seconds - TRACE_SECONDS:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1         # the annotations, little else
            profiled.update({"from": n, "at": time.perf_counter()})
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    # set-up's objects live for the whole run: keep them out of the
    # collector's full passes inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    phases, pauses = [], GcPauses()
    use0 = resource.getrusage(resource.RUSAGE_SELF)
    with pauses:
        ticks, start, end, _ = drive(sysm, producer.get(), producer, first,
                                     b, open_loop, rate, seconds, span,
                                     on_tick, phases)
    use1 = resource.getrusage(resource.RUSAGE_SELF)
    gc.unfreeze()
    if profiled["from"] is not None:
        jax.profiler.stop_trace()
    traced = profiled["from"] or 0
    producer.close()
    jax.monitoring.unregister_event_duration_listener(on_event)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in sysm.devices)
    if compiles or sysm.compiled() != compiled_before:
        raise RuntimeError(f"the step compiled inside the window: "
                           f"{compiles[:4]}, executables "
                           f"{compiled_before} -> {sysm.compiled()}")
    prog_counters = sysm.counters()
    if trace:
        t_hlo = time.perf_counter()
        scopes = TR.scopes_of([sysm.hlo_text(cfg)])
        log(f"compiled module text for the trace's scopes: "
            f"{time.perf_counter() - t_hlo:.1f} s", file=sys.stderr)
    phase_names = sysm.phase_names
    dev = sysm.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(sysm.devices), "memory_peak_bytes": int(peak)}
    del sysm

    # -- the plain reference over every tick, then the comparison ------
    t_ref = time.perf_counter()
    cmp = dep.Comparison()
    newest = {}
    with dep.Reference(cfg, traffic, seed, gen) as ref:
        for t in sorted(outs):
            full, prog = outs[t]
            r = ref.tick(t, full)
            cmp.tick(prog, r, full)
            if t >= first:
                newest[t] = dep.newest_events(r, cfg)
        ref_counters = ref.counters()
    off = cmp.counters(prog_counters, ref_counters)
    if info is not None:
        info.update(program=prog_counters, reference=ref_counters)
    ref_s = time.perf_counter() - t_ref
    ref_log = getattr(ref, "log", "")
    log(f"reference: {len(outs)} ticks x {shards} shards in {ref_s:.1f} s"
        + (f"; {ref_log}" if ref_log else ""), file=sys.stderr)
    if off:
        log(f"counters that differ from the reference: {off}",
            file=sys.stderr)

    # -- end-to-end metrics (host clock) --------------------------------
    n_win = len(ticks)
    lateness = np.array([d - u for _, u, d, _ in ticks]) if ticks \
        else np.zeros(1)
    serve = np.array([e - d for _, _, d, e in ticks]) if ticks \
        else np.zeros(1)
    unix0 = time.time() - time.perf_counter() + (ticks[0][2] if ticks
                                                 else 0.0)
    log(f"window: {n_win} ticks in {seconds} s ({traffic['loop']} loop, "
        f"first dispatch at unix time {unix0:.3f}); "
        f"generator late p50 {1e3 * np.median(lateness):.3f} ms, p95 "
        f"{1e3 * np.percentile(lateness, 95):.3f} ms, max "
        f"{1e3 * lateness.max():.3f} ms; dispatch to emission p50 "
        f"{1e3 * np.median(serve):.3f} ms, p99 "
        f"{1e3 * np.percentile(serve, 99):.3f} ms, max "
        f"{1e3 * serve.max():.3f} ms, "
        f"{int((serve > 2 * np.median(serve)).sum())} ticks over twice "
        f"the median", file=sys.stderr)
    log(slow_ticks(ticks, phases, phase_names, producer.made_s, use0, use1)
        + f"; {pauses}", file=sys.stderr)
    values = {"setup_s": setup_s,
              "events_per_s": sum(e <= end for *_, e in ticks) * shards * b
              / seconds}
    if open_loop and ticks:
        lat = emission_latencies(ticks, newest, start, first, b, rate)
        for m in metrics_of(bench["end_to_end"], wl["name"]):
            q = re.fullmatch(r"emit_p(\d+)_ms", m["name"])
            if q:
                values[m["name"]] = float(
                    np.percentile(lat, int(q.group(1))) * 1e3)
        log(f"windows emitted in the window: {lat.size}", file=sys.stderr)

    result = {"correct": cmp.correct, "attempted": n_win,
              "failed": cmp.bad_ticks, "metrics": {}, "device": device}
    if not trace:
        for m in metrics_of(bench["end_to_end"], wl["name"]):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        spans = [sp for sp in tracer.spans
                 if start <= sp[1] and sp[2] <= profiled["at"]]
        metrics, breakdown = traced_metrics(
            bench, wl, cfg, traffic, trace_dir, scopes, ticks[:traced],
            spans, device, log, dep.work(cfg))
        result["metrics"], result["breakdown"] = metrics, breakdown
    checks = cmp.checks()
    result["checks"] = checks
    lines = [f"check {k}: {v['value']} (limit {v['limit']})"
             for k, v in checks.items()]
    return result, lines


class GcPauses:
    """The collector's passes while inside: how many, their total and
    longest seconds."""

    def __init__(self):
        self.n, self.total, self.longest, self._t = 0, 0.0, 0.0, 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            d = time.perf_counter() - self._t
            self.n, self.total = self.n + 1, self.total + d
            self.longest = max(self.longest, d)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def __str__(self):
        return (f"collector passes {self.n}, {1e3 * self.total:.2f} ms in "
                f"all, longest {1e3 * self.longest:.2f} ms")


def slow_ticks(ticks, phases, names, made_s, use0, use1) -> str:
    """Where the slowest ticks of the window spent their time: each of
    the eight slowest over twice the median (dispatch to emission), by
    phase, with the wait for the generator before it; the generator's
    time per batch; and the process's context switches, page faults and
    CPU seconds over the window."""
    import numpy as np

    if not ticks:
        return "no ticks in the window"
    serve = np.array([e - d for *_, d, e in ticks])
    slow = [i for i in np.argsort(-serve)[:8]
            if serve[i] > 2 * np.median(serve)]
    parts = []
    for i in sorted(slow):
        ph = ", ".join(f"{n} {1e3 * v:.1f}" for n, v in
                       zip(("wait",) + tuple(names), phases[i]))
        parts.append(f"#{i} at +{ticks[i][2] - ticks[0][2]:.3f} s "
                     f"{1e3 * serve[i]:.1f} ms ({ph})")
    made = np.array(made_s or [0.0])
    return (f"slow ticks (of {len(ticks)}): "
            + ("; ".join(parts) or "none")
            + f"; generator per batch p50 {1e3 * np.median(made):.2f} ms, "
            f"max {1e3 * made.max():.2f} ms; over the window: context "
            f"switches {use1.ru_nvcsw - use0.ru_nvcsw} voluntary, "
            f"{use1.ru_nivcsw - use0.ru_nivcsw} involuntary, page faults "
            f"{use1.ru_minflt - use0.ru_minflt} minor, "
            f"{use1.ru_majflt - use0.ru_majflt} major, CPU "
            f"{use1.ru_utime - use0.ru_utime:.2f} s user, "
            f"{use1.ru_stime - use0.ru_stime:.2f} s system")


def emission_latencies(ticks, newest, start: float, first: int,
                       rows: int, rate: float):
    """Per emitted result of every tick: its emission (the tick's
    outputs on the host) less the creation of its newest event;
    ``newest[t]`` as the deployment's ``newest_events`` gives it."""
    import numpy as np

    lat = []
    for t, _, _, emitted_at in ticks:
        event = newest[t]
        created = start + (event[event >= 0] - first * rows) / rate
        lat.append(emitted_at - created)
    return np.concatenate(lat)


def traced_metrics(bench, wl, cfg, traffic, trace_dir, scopes, ticks,
                   spans, device, log, work):
    """The cell's per-layer metrics and the breakdown, from the
    profiler's trace, the unprofiled ticks and the program's spans;
    ``work`` is the deployment's counts for the roofline readers.  Adds
    ``busy_s`` and ``window_s`` to ``device``."""
    import numpy as np

    from bench import trace_reduce as TR

    path = next(trace_dir.glob("**/*.xplane.pb"))
    # host spans that label the device's idle gaps in the breakdown
    names = {"bench.tick", "bench.h2d", "bench.d2h", "stream.step",
             "stream.dispatch", "stream.operands", "stream.call",
             "fleet.step", "fleet.dispatch", "fleet.operands", "fleet.call",
             "fleet.device_execute", "control.tick", "control.pull",
             "control.decide", "stream_step", "fleet_tick"}
    tr = TR.load(path, names.__contains__, scopes)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tick_spans = sorted((a, e) for n, a, e in tr.host if n == "bench.tick")
    lo, hi = tick_spans[0][0], tick_spans[-1][1]
    peaks = load_json("peaks.json")["devices"]
    if device["kind"] not in peaks:
        raise RuntimeError(f"no peaks for device kind {device['kind']!r} "
                           f"in bench/peaks.json")
    ctx = Ctx(cfg=cfg, traffic=traffic, ticks=ticks, spans=spans, trace=tr,
              window=(lo, hi), traced_ticks=len(tick_spans),
              peak=peaks[device["kind"]], work=work,
              log=lambda *a: log(*a, file=sys.stderr))
    metrics = {}
    for m in metrics_of(bench["per_layer"], wl["name"]):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["busy_s"] = float(np.mean(
        [TR.busy_ns(d.ops, lo, hi) for d in tr.devices])) / 1e9
    device["window_s"] = (hi - lo) / 1e9
    ops, gaps = {}, {}
    for d in tr.devices:
        for k, v in TR.op_ns(d.ops, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(tr.devices) / 1e9
        for k, v in TR.idle_gaps(d.ops, tr.host, lo, hi):
            gaps[k] = gaps.get(k, 0.0) + v / len(tr.devices) / 1e9
    log(f"traced {len(tick_spans)} ticks over {(hi - lo) / 1e9:.3f} s, "
        f"after {len(ticks)} unprofiled ticks", file=sys.stderr)
    return metrics, {
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: the program under test is missing "
              f"({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    bench, wl, cfg, traffic = cell_spec(args.workload)
    import jax

    # the compile cache lives at a fixed path inside this checkout
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["chips"]:
        print(f"bench/run.py: {wl['name']} needs {wl['chips']} TPU chips, "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    if cfg["shards"] != wl["chips"]:
        raise SystemExit(f"{wl['name']}: {cfg['shards']} shards on "
                         f"{wl['chips']} chips")
    result, lines = run_cell(bench, wl, cfg, traffic, args.seed,
                             args.seconds, bool(args.trace))
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
