"""chipbench — the benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of BENCHMARK.json: refuse to run without the
TPUs the cell asks for, build the cell's data from --seed, install the
device path as `Node.__init__` does with `[tpu] enable = true`, warm the
cell's own shapes, measure a closed loop for --seconds, compare every
verdict with the plain reference, print one JSON line. Everything
before the window is `setup_s`.

The harness knows no cell by name. A cell names a configuration (a file
of sizes, which names its plain reference) and a traffic mix (a file of
parameters, which names its driver under drivers/); the per-layer
metrics are one reader each under layer_metrics/, found by the metric's
name. chipbench/README.md says how to add each as a file.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PROBE_WAIT_S = 600.0  # bound on the install's sr25519 probe (it compiles)
TRACE_DIR = os.path.join(HERE, ".trace")  # listed in .gitignore


class Refused(Exception):
    """The run cannot be made here: exit non-zero, print no result."""


# -- the manifest and the files it names ------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> types.SimpleNamespace:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def reports(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return types.SimpleNamespace(
        name=workload,
        chips=cell["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"] if reports(m)],
        per_layer=[m for m in manifest["per_layer"] if reports(m)],
    )


def load_module(subdir: str, name: str):
    """chipbench/<subdir>/<name>.py, by file: a metric's name may hold
    characters a module's cannot."""
    path = os.path.join(HERE, subdir, name + ".py")
    if not os.path.exists(path):
        raise Refused(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{subdir}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the device -------------------------------------------------------


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but the TPUs the
    cell asks for. There is no CPU arm."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise Refused(
            f"needs {chips} TPU chip(s); jax found {len(devs)} x "
            f"{devs[0].platform!r} ({devs[0].device_kind})"
        )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def _memory_peaks() -> list:
    """Each chip's peak_bytes_in_use, in jax.devices()'s order."""
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()]


def memory_peak_bytes() -> int:
    """The peak on the fullest chip."""
    return max(_memory_peaks())


def memory_peak_device() -> int:
    """Which chip that is: its index in jax.devices()."""
    peaks = _memory_peaks()
    return peaks.index(max(peaks))


def install_device_path(config: dict) -> dict:
    """What Node.__init__ does when `[tpu] enable = true`
    (node/node.py; chip_smoke.phase_install): the compile cache, the
    verifier factories at the config's default min_batch, the merkle
    hooks. A configuration with `devices` > 1 installs over a mesh."""
    import jax

    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto import tpu_verifier
    from tendermint_tpu.ops import compile_cache, merkle_kernel

    mesh = None
    if config.get("devices", 1) > 1:
        from tendermint_tpu.parallel import make_mesh

        mesh = make_mesh(jax.devices()[: config["devices"]])
    cache_dir = compile_cache.enable()
    min_batch = Config().tpu.min_batch_size
    tpu_verifier.install(min_batch=min_batch, mesh=mesh)
    merkle_kernel.install()
    chunk = (
        tpu_verifier._TpuBatchVerifier.STREAM_CHUNK
        if tpu_verifier.on_accelerator()
        else None
    )
    return {"cache_dir": cache_dir, "min_batch": min_batch, "chunk": chunk}


def wait_for_probe() -> None:
    """The install compiles the smallest sr25519 bucket on a thread of
    its own and closes the single-verify breaker when it is done. The
    window must not share the chip or the host with that."""
    from tendermint_tpu.crypto import breaker, tpu_verifier

    single = tpu_verifier.sr_single_breaker()
    deadline = time.monotonic() + PROBE_WAIT_S
    while single.state() != breaker.CLOSED:
        if time.monotonic() > deadline:
            raise RuntimeError("the install's sr25519 probe never finished")
        time.sleep(0.05)


# -- the program's own counters ---------------------------------------

COUNTERS = (
    "batches", "sigs", "faults", "warm_misses", "pad_waste",
    "cache_hits", "cache_misses", "memo_hits", "memo_misses",
)  # fmt: skip


def make_counter_reader():
    from tendermint_tpu.crypto import sigcache, tpu_verifier

    def read() -> tuple:
        t, s = tpu_verifier.stats(), sigcache.stats()
        return (
            t["batches"], t["sigs"], t["faults"], t["warm_misses"], t["pad_waste"],
            s["hits"], s["misses"], s["commit_hits"], s["commit_misses"],
        )  # fmt: skip

    return read


class CompileLog:
    """What JAX compiled or loaded, and when, from jax.monitoring's own
    events (a copy of chip_smoke.CompileLog that keeps the times). The
    duration event spans compile-or-load: on a persistent-cache hit it
    is the seconds the executable took to read back."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.events: list = []  # (perf_counter at the end, seconds)
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> list:
        return [(t, s) for t, s in self.events if t0 <= t <= t1]


# -- the window -------------------------------------------------------


def run_window(driver, seconds: float, read_counters, tracer=None) -> dict:
    """The closed loop: one caller, the next request when the last has
    its verdict. A request that starts inside the window is finished and
    counted, and the window's wall time runs to its end. A traced run
    then goes on, the same loop unbroken, for the few requests the
    profiler watches; they are judged with the window's."""
    tokens, verdicts, starts, ends, counters = [], [], [], [], []
    clock = time.perf_counter
    before = read_counters()

    def request(i: int, until: float, annotate=None) -> bool:
        token = driver.window_request(i)
        t0 = clock()
        if t0 >= until:
            return False
        if annotate:
            with annotate("cb_request"):
                verdict = driver.run(token, annotate)
        else:
            verdict = driver.run(token)
        t1 = clock()
        tokens.append(token)
        verdicts.append(verdict)
        starts.append(t0)
        ends.append(t1)
        counters.append(read_counters())
        return True

    t_open = clock()
    while request(len(tokens), t_open + seconds):
        pass
    wall = clock() - t_open
    if tracer:
        with tracer.profiling():
            for _ in range(tracer.requests):
                request(len(tokens), math.inf, tracer.annotate)
    return {
        "tokens": tokens, "verdicts": verdicts, "starts": starts, "ends": ends,
        "counters": [before] + counters, "t_open": t_open, "wall_s": wall,
    }  # fmt: skip


def judge(driver, window: dict, installed: dict, log: CompileLog) -> dict:
    """Hold every request of the window to the reference's verdict and
    to device_accounting's conditions (chip_smoke.py): the program's
    counters moved by exactly what the request sent, no fault, no
    first-touch bucket, no compile, and exactly the verified-signature
    and commit-memo hits the driver's `hits(token)` says the deployment
    produces: (0, 0) for a driver without one, whose cell is cold by
    construction. A hit too many is a ring gone warm; a hit too few is
    a cache that stopped working, a different result and not a slower
    one."""
    n = len(window["tokens"])
    hits = getattr(driver, "hits", lambda token: (0, 0))
    wrong, bypassed, compiled = [], [], []
    compile_ends = [t for t, _s in log.between(window["t_open"], window["ends"][-1])] if n else []
    expected = driver.expected(window["tokens"])
    for i in range(n):
        token = window["tokens"][i]
        if window["verdicts"][i] != expected[i]:
            wrong.append(i)
        delta = dict(
            zip(COUNTERS, (b - a for a, b in zip(window["counters"][i], window["counters"][i + 1])))
        )
        batches, sigs = driver.sent(token, installed["min_batch"], installed["chunk"])
        cache_hits, memo_hits = hits(token)
        if (
            delta["batches"] != batches
            or delta["sigs"] != sigs
            or delta["faults"]
            or delta["warm_misses"]
            or delta["cache_hits"] != cache_hits
            or delta["memo_hits"] != memo_hits
        ):
            bypassed.append(i)
        if any(window["starts"][i] <= t <= window["ends"][i] for t in compile_ends):
            compiled.append(i)
    from tendermint_tpu.crypto import breaker

    open_breakers = [
        name
        for name in ("ed25519", "sr25519")
        if breaker.breaker_for(name).state() != breaker.CLOSED
    ]
    failed = sorted(set(wrong) | set(bypassed) | set(compiled))
    return {
        "attempted": n,
        "failed": failed,
        "wrong": wrong,
        "bypassed": bypassed,
        "compiled": compiled,
        "open_breakers": open_breakers,
        "corrupted_requests": sum(1 for t in window["tokens"] if t[1]),
    }


def percentile(values: list, q: float) -> float:
    """The value at rank ceil(q * n) of the sorted sample."""
    ranked = sorted(values)
    return ranked[max(0, math.ceil(q * len(ranked)) - 1)]


def end_to_end(window: dict, verdict: dict, setup_s: float) -> tuple:
    """(the numbers a user of the node sees, the latency distribution's
    shape). A failed request completes nothing and counts as missing in
    the tail: it is given the whole window's length as its latency."""
    n = verdict["attempted"]
    failed = set(verdict["failed"])
    wall_ms = window["wall_s"] * 1e3
    lat_ms = [
        wall_ms if i in failed else (window["ends"][i] - window["starts"][i]) * 1e3
        for i in range(n)
    ]
    values = {
        "commits_per_s": (n - len(failed)) / window["wall_s"],
        "verify_p95_ms": percentile(lat_ms, 0.95),
        "setup_s": setup_s,
    }
    shape = {f"p{round(q * 100)}": percentile(lat_ms, q) for q in (0.5, 0.9, 0.95, 0.99)}
    shape["max"] = max(lat_ms)
    return values, shape


# -- the traced run ---------------------------------------------------


class Tracer:
    """`--trace 1`: the program's span recorder and the collector's
    callbacks on for the whole window, and JAX's profiler over the
    `trace_requests` requests that follow it — a 128-lane tile alone is
    ~24,000 device events an execution and stopping the profiler takes
    tens of seconds, so a trace holds a few requests, after the window
    and not inside it. Python's own tracer is off (it doubles a
    request's host time); host annotations go into the profiler's trace,
    so the reducer can say what the host was doing in the device's idle
    gaps."""

    def __init__(self, traffic: dict) -> None:
        import jax

        from tendermint_tpu.libs import trace

        self._jax = jax
        self._spans = trace
        self.requests = traffic["trace_requests"]
        self.dir = TRACE_DIR
        shutil.rmtree(self.dir, ignore_errors=True)
        trace.reset()
        trace.enable(capacity=1 << 20)
        self.gc_pauses: list = []  # (perf_counter at the end, seconds, generation)
        self._gc_start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        else:
            self.gc_pauses.append((now, now - self._gc_start, info["generation"]))

    def annotate(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def profiling(self):
        options = self._jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        self._jax.profiler.start_trace(self.dir, profiler_options=options)
        try:
            yield
        finally:
            self._jax.profiler.stop_trace()

    def spans(self) -> list:
        gc.callbacks.remove(self._on_gc)
        self._spans.disable()
        return self._spans.snapshot()

    def xplane(self):
        for base, _dirs, files in os.walk(self.dir):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None


def inside_requests(pauses: list, window: dict) -> list:
    """The collector's pauses that ended inside a request of the window
    (the profiler's own start and stop allocate, between requests)."""
    out = []
    for pause in pauses:
        at = bisect.bisect_right(window["starts"], pause[0]) - 1
        if at >= 0 and pause[0] <= window["ends"][at]:
            out.append(pause)
    return out


def per_layer(cell, driver, window: dict, verdict: dict, tracer: Tracer,
              log: CompileLog, device: dict) -> tuple:
    """(metrics, device additions, breakdown) of a traced run: each
    metric from its own reader, left out where the reader finds nothing
    to read."""
    from chipbench import trace_reduce, work

    peaks = load_json(os.path.join(HERE, "peaks.json")).get(device["kind"])
    if peaks is None:
        raise Refused(f"no peaks for device kind {device['kind']!r} in peaks.json")
    path = tracer.xplane()
    reduced = trace_reduce.reduce_file(path) if path else None
    n = verdict["attempted"]
    first, last = window["counters"][0], window["counters"][-1]
    t_last = window["ends"][-1] if n else window["t_open"]
    ctx = types.SimpleNamespace(
        config=cell.config,
        driver=driver,
        requests=n,
        tokens=window["tokens"],
        entry_s=[e - s for s, e in zip(window["starts"], window["ends"])],
        counters=dict(zip(COUNTERS, (b - a for a, b in zip(first, last)))),
        spans=tracer.spans(),
        gc_pauses=inside_requests(tracer.gc_pauses, window),
        trace=reduced,
        compiles_in_window=log.between(window["t_open"], t_last),
        compiles_in_setup=log.between(_T_PROCESS, window["t_open"]),
        work=work,
        peaks=peaks,
    )
    metrics = {}
    for m in cell.per_layer:
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra, breakdown = {}, None
    if reduced:
        extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
        breakdown = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    return metrics, extra, breakdown


# -- one run ----------------------------------------------------------


def run_cell(args, prepare=None) -> dict:
    """One run of one cell. `prepare(driver)` is for control.py and the
    tests, which break the timed path before the window to see the
    comparison fail; run.py's own command never passes it."""
    cell = load_cell(args.workload)
    device = require_tpu(cell.chips)
    log = CompileLog()
    setup = {}

    t = time.perf_counter()
    driver = load_module("drivers", cell.traffic["driver"]).setup(
        cell.config, cell.traffic, args.seed
    )
    setup["data_s"] = time.perf_counter() - t

    t = time.perf_counter()
    installed = install_device_path(cell.config)
    read_counters = make_counter_reader()
    warm_tokens = driver.warmup_requests()
    warm_verdicts = [driver.run(token) for token in warm_tokens]
    wait_for_probe()
    setup["warmup_s"] = time.perf_counter() - t
    if prepare is not None:
        prepare(driver)

    tracer = Tracer(cell.traffic) if args.trace else None
    setup_s = time.perf_counter() - _T_PROCESS
    window = run_window(driver, args.seconds, read_counters, tracer)
    device["memory_peak_bytes"] = memory_peak_bytes()
    device["memory_peak_device"] = memory_peak_device()

    # the reference runs only now: the window has closed and the
    # device's peak is read, and none of its time is set-up
    t = time.perf_counter()
    verdict = judge(driver, window, installed, log)
    warm_wrong = sum(
        got != want for got, want in zip(warm_verdicts, driver.expected(warm_tokens))
    )
    setup["reference_s"] = time.perf_counter() - t
    checks = {
        "verdict_mismatches": {"value": len(verdict["wrong"]), "limit": 0},
        "warmup_verdict_mismatches": {"value": warm_wrong, "limit": 0},
        "bypassed_requests": {"value": len(verdict["bypassed"]), "limit": 0},
        "open_breakers": {"value": len(verdict["open_breakers"]), "limit": 0},
        "corrupted_requests_min": {
            "value": verdict["corrupted_requests"], "limit": 1, "at_least": True,
        },
    }  # fmt: skip
    correct = all(
        c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
        for c in checks.values()
    )
    result = {
        "correct": correct,
        "attempted": verdict["attempted"],
        "failed": len(verdict["failed"]),
    }
    if args.trace:
        metrics, extra, breakdown = per_layer(cell, driver, window, verdict, tracer, log, device)
        device.update(extra)
        result["metrics"] = metrics
        result["device"] = device
        if breakdown:
            result["breakdown"] = breakdown
    else:
        values, latency_ms = end_to_end(window, verdict, setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
        result["device"] = device
        result["latency_ms"] = latency_ms  # the tail's shape, for a reader
    result["setup"] = dict(setup, setup_s=setup_s, cache_dir=installed["cache_dir"])
    result["checks"] = checks
    for name, c in checks.items():
        op = ">=" if c.get("at_least") else "<="
        print(f"chipbench: {name} = {c['value']} (limit {op} {c['limit']})", file=sys.stderr)
    print(f"chipbench: correct = {correct}", file=sys.stderr)
    log.close()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except ImportError as e:  # a directory without the program under test
        print(f"chipbench: cannot import the program: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
