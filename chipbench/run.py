#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name: ``BENCHMARK.json`` at the checkout's root,
``chipbench/configs/<config>.json``, ``chipbench/traffic/<mix>.json``,
``chipbench/metrics/<metric>.py`` and ``chipbench/cost/<kernel>.py``.

One run:

1. set-up: the archive is made from ``--seed`` (field on the device,
   written through ``RadarArchive.append_scan``, catalogued) in a
   temporary directory under ``$TMPDIR``, removed at exit;
   ``ArchiveService`` + ``ArchiveServer`` start in this process, which
   holds the chip; every request shape the mix can send is computed once
   by a separate service (programs compiled or read from the persistent
   cache, gate maps built), and each tenant's sessions are opened;
2. window: a child process (``loadgen.py``, no JAX) sends the mix's
   requests open loop for ``--seconds``; requests still open at the
   close are waited for up to a minute; with ``--trace 1`` the profiler
   records the window;
3. check: a sample of the served bodies, drawn from the seed and holding
   each class's longest window, is compared with the plain reference
   (``reference.py``) under ``limits.json``;
4. the last line of standard output is the result: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
   or with ``--trace 1`` its per-layer ones), ``device``, with
   ``--trace 1`` ``breakdown``, and last ``checks``, each compared number
   beside its limit (also the last lines of standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``chipbench/.jax_cache``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import resource
import shutil
import struct
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional
from urllib.parse import parse_qs, urlsplit

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 60.0
WARM_THREADS = 8


class SetupError(Exception):
    """The run cannot start: no chip, an unknown device, a bad cell."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> SimpleNamespace:
    """The cell ``workload`` and everything its names point to."""
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in doc["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    bench = root / doc["paths"][0]
    mix = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]), cfg=cfg, mix=mix,
        bench=bench,
        archive={"n_scans": cfg["n_scans"]},
        end_to_end=[m for m in doc["end_to_end"] if mine(m)],
        per_layer=[m for m in doc["per_layer"] if mine(m)],
        limits=json.loads((bench / "limits.json").read_text()),
        peaks=json.loads((bench / "peaks.json").read_text()))


def require_chip(chips: int) -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise SetupError(f"no TPU: JAX's default backend is "
                         f"{jax.default_backend()!r}")
    if len(jax.devices()) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(jax.devices())}")


def init(cell) -> List[Any]:
    """Paths, the chip, the compile cache; the devices the cell uses."""
    for path in (str(ROOT / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    import repro.serve.http  # noqa: F401  the system under test, or fail

    require_chip(cell.chips)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()[: cell.chips]
    if devices[0].device_kind not in cell.peaks["devices"]:
        raise SetupError(f"device_kind {devices[0].device_kind!r} is not "
                         "in peaks.json")
    return devices


def emit(doc: Dict[str, Any]) -> None:
    print(json.dumps(doc), flush=True)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, -int(-q * len(s) // 1) - 1)]


def _split(path: str):
    url = urlsplit(path)
    return url.path.rstrip("/").split("/")[-1], parse_qs(url.query)


def warm(catalog, cell) -> int:
    """Compute every request shape of the mix once, on a service of its
    own, so that the measured one keeps a cold product cache."""
    from chipbench import traffic
    from repro.serve.http import ArchiveService

    reqs = traffic.warm_set(cell.mix)
    service = ArchiveService(catalog)
    try:
        with ThreadPoolExecutor(max_workers=WARM_THREADS) as pool:
            list(pool.map(lambda r: service.product(*_split(r["path"]),
                                                    "chipbench-warm"), reqs))
    finally:
        service.close()
    return len(reqs)


class Served:
    """The system under test: service, probes and server on a catalog,
    with each tenant's sessions open."""

    def __init__(self, catalog, tenants) -> None:
        from chipbench import probes
        from repro.serve.http import ArchiveServer, ArchiveService

        self.service = ArchiveService(catalog)
        self.probe = probes.Probes(self.service, catalog)
        self.server = ArchiveServer(self.service).start()
        for tenant in tenants:
            for repo_id in catalog.repository_ids():
                self.service.session(tenant, repo_id)

    def close(self) -> None:
        self.server.close()
        self.service.close()
        self.probe.close()


def drive(served: Served, reqs, keep, seconds: float, trace_dir=None,
          t_proc: Optional[float] = None) -> SimpleNamespace:
    """One open-loop window against ``served``; the load generator's
    records and kept bodies, and the probes' counters over the window."""
    import jax
    from jax.profiler import TraceAnnotation

    probe, service = served.probe, served.service
    child = subprocess.Popen([sys.executable, str(BENCH / "loadgen.py")],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        if child.stdout.readline() != b"ready\n":
            raise RuntimeError("the load generator did not start")
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        host, port = served.server.address
        t_start = time.monotonic() + 0.1
        setup_s = (time.time() + (t_start - time.monotonic()) - t_proc
                   if t_proc is not None else None)
        child.stdin.write(json.dumps({
            "host": host, "port": port, "t_start": t_start,
            "deadline_s": seconds + DRAIN_S, "keep": keep,
            "requests": [[r["due"], r["path"], r["tenant"]] for r in reqs],
        }).encode() + b"\n")
        child.stdin.close()
        hits0 = service.stats()["product_cache"]["hits"]
        fetch0, comp0 = probe.chunk_fetches(), probe.meter.snapshot()
        time.sleep(max(0.0, t_start - time.monotonic()))
        probe.recording = True
        with TraceAnnotation("chipbench.window"):
            head = json.loads(child.stdout.readline())
            bodies = {}
            for i in head["kept"]:
                (n,) = struct.unpack(">Q", child.stdout.read(8))
                bodies[i] = child.stdout.read(n)
        probe.recording = False
        if trace_dir is not None:
            jax.profiler.stop_trace()
        if child.wait() != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    comp1 = probe.meter.snapshot()
    records = [dict(zip(("status", "late_s", "latency_s", "nbytes", "crc32"),
                        rec), **req)
               for rec, req in zip(head["records"], reqs)]
    return SimpleNamespace(
        records=records, bodies=bodies, setup_s=setup_s,
        cache_hits=service.stats()["product_cache"]["hits"] - hits0,
        chunk_fetches=probe.chunk_fetches() - fetch0,
        compiles=(comp1["compiles"] - comp0["compiles"]
                  + comp1["cache_hits"] - comp0["cache_hits"]))


def end_to_end(win, seconds: float) -> Dict[str, float]:
    """The end-to-end metrics over every request due in the window."""
    ok = [r for r in win.records if r["status"] == 200]
    # a request never answered waited at least until the drain ended
    lat = [r["latency_s"] if r["status"] == 200 else seconds + DRAIN_S
           for r in win.records]
    return {"products_per_s": sum(1 for r in ok if r["due"] + r["latency_s"]
                                  <= seconds) / seconds,
            "latency_p50_s": percentile(lat, 0.50),
            "latency_p90_s": percentile(lat, 0.90),
            "setup_s": win.setup_s}


def window_line(win, seconds: float) -> Dict[str, Any]:
    """Counters beside the metrics: lateness, failures, per-class tails."""
    late = [r["late_s"] for r in win.records]
    classes = {}
    for key in sorted({(r["cls"], r["length"]) for r in win.records}):
        lat = [r["latency_s"] if r["status"] == 200 else seconds + DRAIN_S
               for r in win.records if (r["cls"], r["length"]) == key]
        classes[f"{key[0]}.{key[1]}"] = {"n": len(lat),
                                         "p50_s": percentile(lat, 0.5),
                                         "max_s": max(lat)}
    return {"phase": "window", "requests": len(win.records),
            "failed": sum(1 for r in win.records if r["status"] != 200),
            "late_p50_s": percentile(late, 0.5), "late_max_s": max(late),
            "compiles_in_window": win.compiles, "classes": classes}


def check(data, reqs, keep, win, limits) -> SimpleNamespace:
    """Compare the kept bodies with the reference; judge every number."""
    from chipbench import reference

    sample = [i for i in keep if win.records[i]["status"] == 200]
    numbers = reference.check_sample(reference.Reference(data),
                                     [reqs[i]["path"] for i in sample],
                                     [win.bodies[i] for i in sample])
    numbers["failed"] = float(sum(1 for r in win.records
                                  if r["status"] != 200))
    correct, table = reference.judge(numbers, limits)
    return SimpleNamespace(correct=bool(correct and sample), table=table,
                           compared=len(sample), numbers=numbers)


class Context(SimpleNamespace):
    """What a per-layer metric reader reads."""

    def roofline(self, kernel: str) -> Optional[float]:
        calls = [c for c in self.kernel_calls if c["kernel"] == kernel]
        path = self.bench / "cost" / f"{kernel}.py"
        if not calls or self.trace is None or not path.exists():
            return None
        cost = _load_module(path, f"chipbench_cost_{kernel}")
        device_s = self.trace.program_seconds(cost.PROGRAM)
        if not device_s:
            return None
        least = flops_s = bytes_s = 0.0
        for c in calls:
            ops, nbytes = cost.cost(c["shapes"], c["kwargs"])
            f = ops / self.peak["flops_per_s"]
            b = nbytes / self.peak["hbm_bytes_per_s"]
            least, flops_s, bytes_s = least + max(f, b), flops_s + f, \
                bytes_s + b
        self.bounds[kernel] = {
            "calls": len(calls), "least_s": least, "device_s": device_s,
            "bound": "hbm" if bytes_s >= flops_s else "flops",
            "flops_s": flops_s, "hbm_s": bytes_s}
        return 100.0 * least / device_s


def per_layer(cell, served, win, summary, kind) -> SimpleNamespace:
    """Every per-layer metric of the cell whose reader finds something."""
    probe = served.probe
    ctx = Context(requests=win.records, products=probe.products,
                  computed=probe.computed, compute_s=probe.compute_s,
                  cache_hits=win.cache_hits,
                  chunk_fetches=win.chunk_fetches,
                  h2d_bytes=probe.h2d_bytes, compiles=win.compiles,
                  trace=summary, kernel_calls=probe.kernel_calls,
                  peak=cell.peaks["devices"][kind], bench=cell.bench,
                  bounds={})
    metrics = {}
    for m in cell.per_layer:
        mod = _load_module(cell.bench / "metrics" / f"{m['name']}.py",
                           "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return SimpleNamespace(metrics=metrics, bounds=ctx.bounds)


def run(args, root: Path = ROOT) -> Dict[str, Any]:
    """One run of a cell; returns the result line's object."""
    import psutil

    t_proc = psutil.Process().create_time()
    cell = load_cell(root, args.workload)
    devices = init(cell)
    kind = devices[0].device_kind
    from chipbench import archive, probes, traces, traffic

    meter = probes.CompileMeter()
    reqs = traffic.schedule(cell.mix, cell.archive, args.seed, args.seconds)
    keep = traffic.sample(reqs, int(cell.mix["compare"]), args.seed)
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    served = None
    try:
        t0 = time.perf_counter()
        data = archive.generate(cell.cfg, args.seed)
        t1 = time.perf_counter()
        catalog = archive.build(cell.cfg, data, workdir)
        t2 = time.perf_counter()
        n_warm = warm(catalog, cell)
        served = Served(catalog, cell.mix["tenants"])
        t3 = time.perf_counter()
        compiled = meter.snapshot()
        trace_dir = os.path.join(workdir, "trace") if args.trace else None
        win = drive(served, reqs, keep, args.seconds, trace_dir, t_proc)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        summary = (traces.summarize(traces.load(traces.find_xplane(trace_dir)))
                   if args.trace else None)
        layers = (per_layer(cell, served, win, summary, kind)
                  if args.trace else None)
        served.close()
        served = None

        emit(dict(window_line(win, args.seconds),
                  setup={"generate_s": t1 - t0, "build_s": t2 - t1,
                         "warm_s": t3 - t2, "warm_requests": n_warm,
                         "compile": compiled},
                  memory_peak_bytes=peak,
                  host_rss_peak_bytes=resource.getrusage(
                      resource.RUSAGE_SELF).ru_maxrss * 1024))
        verdict = check(data, reqs, keep, win, cell.limits)
        emit({"phase": "check", "compared": verdict.compared,
              "sampled": len(keep)})
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result: Dict[str, Any] = {
            "correct": verdict.correct, "attempted": len(win.records),
            "failed": int(verdict.table["failed"]["value"])}
        if args.trace:
            emit({"phase": "trace", "rooflines": layers.bounds,
                  "program_s": summary.program_s, "busy_s": summary.busy_s,
                  "window_s": summary.window_s})
            device.update(busy_s=summary.mean_busy_s,
                          window_s=summary.window_s)
            result.update(metrics=layers.metrics, device=device, breakdown={
                "device_ops": [list(x) for x in summary.top_ops],
                "idle_gaps": [list(x) for x in summary.idle_gaps]})
        else:
            e2e = end_to_end(win, args.seconds)
            result.update(metrics={m["name"]: {"value": float(e2e[m["name"]]),
                                               "unit": m["unit"]}
                                   for m in cell.end_to_end}, device=device)
        result["checks"] = verdict.table
        return result
    finally:
        if served is not None:
            served.close()
        shutil.rmtree(workdir, ignore_errors=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse_args(argv)
    try:
        result = run(args, root)
    except SetupError as exc:
        print(f"chipbench: {exc}; nothing was run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
